"""Kernels: share (%) of the full compare-select expansion that the LSCD
kernels run, each tile's slabs up to its last real word over every tile's
``slots / 8`` slabs (the program's ``lscd_slab_share`` gauge, set once from
the served params, read at the window's close). None where the program has
no such gauge or serves no Tiled-CSL weight."""


def read(rec):
    return rec["counters"]["close"].get("lscd_slab_share") or None
