"""halo_bytes_per_matvec.solve: bytes one chip sends per matvec of the
sharded solve, over all grid axes (the program's repro_mesh_halo_bytes
gauge, set from the block shapes when the sharded program is built);
nothing to read where the program has no such gauge or built no sharded
program."""


def read(record):
    from repro.observe import metrics

    if record.solves is None:
        return None
    halo = metrics.snapshot().get("repro_mesh_halo_bytes")
    if halo is None or not halo["values"]:
        return None
    return sum(v["value"] for v in halo["values"])
