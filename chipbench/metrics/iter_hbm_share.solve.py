"""iter_hbm_share.solve: the least bytes an iteration must move
(chipbench.floor) over what the chip's HBM bandwidth moves in the
device-busy time of one iteration (trace busy time over the iterations
of the solves in the traced part of the window)."""


def read(record):
    t = record.trace
    if t is None or record.solves is None or not record.traced_iterations:
        return None
    busy_per_iter = t.busy_s / record.traced_iterations
    return record.floor_bytes / (record.peaks.hbm_bytes_per_s
                                 * busy_per_iter)
