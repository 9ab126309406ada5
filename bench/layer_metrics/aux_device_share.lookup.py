"""Share of the keys probed in ``T_aux`` whose correction the device
applied from its copy of the resident sorted view, in percent:
``ServeStats.aux_device_keys`` over ``ServeStats.aux_keys`` in the
window, as ``AuxTable.merge_device`` and ``AuxTable.get`` count them.
0 where the view does not fit the pool and the host probes every key.

Returns None where the run has nothing to read: a program that counts
no device keys, or no key probed."""


def read(ctx):
    device, keys = ctx["spans"].get("serve.aux_device_keys"), ctx["spans"].get("serve.aux_keys")
    if device is None or not keys:
        return None
    return 100.0 * device / keys
