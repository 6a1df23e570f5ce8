"""The decode cache one engine allocates: the ``kv_bytes`` and
``ssm_bytes`` attributes of the program's last ``engine.init`` span in the
window, in GB (1e9 bytes).  None where the program records no such
attributes."""

import program_spans

UNIT, BETTER, LAYER, MOVES = "GB", "lower", "cache manager", "tokens_per_s"


def value(run):
    inits = [
        r for r in program_spans.records(run) or []
        if r.name == "engine.init" and "kv_bytes" in r.attrs and "ssm_bytes" in r.attrs
    ]
    if not inits:
        return None
    return (inits[-1].attrs["kv_bytes"] + inits[-1].attrs["ssm_bytes"]) / 1e9
