"""Share of the keys probed in ``T_aux`` that the pool-resident sorted
view answered, in percent: ``ServeStats.aux_resident_keys`` over
``ServeStats.aux_keys`` in the window, as ``AuxTable.get`` counts them
once per call.  0 where the table's decompressed bytes do not fit its
pool and every probe takes the partitioned path.

Returns None where the run has nothing to read: a program that counts
no resident keys, or no key probed."""


def read(ctx):
    resident, keys = ctx["spans"].get("serve.aux_resident_keys"), ctx["spans"].get("serve.aux_keys")
    if resident is None or not keys:
        return None
    return 100.0 * resident / keys
