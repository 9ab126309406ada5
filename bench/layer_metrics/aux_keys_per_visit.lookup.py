"""Keys the ``T_aux`` probe answers per partition visit: keys probed
over partitions visited in the window, as ``AuxTable.get`` counts them
once per call at the ``aux.get`` span (``ServeStats.aux_keys`` and
``aux_visits``).  Each visit costs a pool lookup, a ``searchsorted`` and
fancy indexes, so few keys a visit means the per-visit cost leads.

Returns None where the run has nothing to read."""


def read(ctx):
    keys, visits = ctx["spans"].get("serve.aux_keys"), ctx["spans"].get("serve.aux_visits")
    if keys is None or not visits:
        return None
    return keys / visits
