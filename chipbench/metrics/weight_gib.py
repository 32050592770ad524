"""Format: bytes of the served parameter tree (Tiled-CSL words and tile
counts, dense embedding, norms and biases), in GiB."""


def read(rec):
    return rec["weight_bytes"] / 2 ** 30
