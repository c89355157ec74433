"""exposed_collective_frac.solve: share of device-busy time in which a
collective (psum, halo permute) runs and no compute does, averaged over
the devices; nothing to read where no collective ran."""


def read(record):
    t = record.trace
    if t is None or record.solves is None or t.devices < 2:
        return None
    return t.collective_exposed_s / t.busy_s
