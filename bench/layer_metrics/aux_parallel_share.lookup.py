"""Share of the ``T_aux`` pool misses that the partitioned probe
decompressed on its shared worker threads, in percent:
``ServeStats.aux_parallel`` over ``ServeStats.aux_decompressed`` in the
window, as ``AuxTable.get`` counts them once per call.  A wave of
partitions with a single miss decompresses it on the calling thread.

Returns None where the run has nothing to read: a program that counts
no such misses, or none decompressed."""


def read(ctx):
    parallel = ctx["spans"].get("serve.aux_parallel")
    decompressed = ctx["spans"].get("serve.aux_decompressed")
    if parallel is None or not decompressed:
        return None
    return 100.0 * parallel / decompressed
