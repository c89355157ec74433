"""host_bytes_per_req.serve: bytes the solve engine moved between host
and device, both ways (the program's repro_engine_host_bytes_total), per
request it retired (repro_engine_requests_total).  Both counters are
read once, after the run, so set-up's warm requests and the drain after
the window are in both; nothing to read where the program has no such
counter."""


def read(record):
    from repro.observe import metrics

    if not record.requests:
        return None
    snap = metrics.snapshot()
    moved = snap.get("repro_engine_host_bytes_total")
    retired = sum(v["value"] for v in
                  snap.get("repro_engine_requests_total", {})
                  .get("values", ()))
    if moved is None or not retired:
        return None
    return sum(v["value"] for v in moved["values"]) / retired
