"""Share of the ``T_aux`` partition visits that missed the pool and were
decompressed, in percent: ``ServeStats.aux_decompressed`` over
``ServeStats.aux_visits`` in the window, as ``AuxTable.get`` counts them
once per call.  0 where the resident sorted view answers every probe.

Returns None where the run has nothing to read: a program that counts
no decompressed partitions, or no visit."""


def read(ctx):
    decompressed = ctx["spans"].get("serve.aux_decompressed")
    visits = ctx["spans"].get("serve.aux_visits")
    if decompressed is None or not visits:
        return None
    return 100.0 * decompressed / visits
