"""occupancy.serve: slot-chunks the retired requests held
(sum of telemetry.chunks_resident) over the slot-chunks stepped
(chunks x max_batch)."""


def read(record):
    if not record.requests or not record.chunks:
        return None
    held = sum(r.chunks_resident for r in record.requests)
    return held / (record.chunks * record.max_batch)
