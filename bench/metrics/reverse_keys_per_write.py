"""Post-processing (``core/store.py``): keys the PBA -> LBA reverse index
walked to take the keys staged since its last update, over the writes the
engines applied, in the window (the program's ``reverse_keys_walked``
counters against ``engine_writes``).  About 1 where each staged key is
walked once; a whole-map rebuild per pass reads the mapped volume over the
period.  Silent where the family reads no such counter."""


def read(ctx):
    c = ctx["counters"]
    writes = c.get("engine_writes", 0)
    if "reverse_keys" not in c or not writes:
        return None
    return c["reverse_keys"] / writes
