"""Bytes one chip must send through the ring in an iteration, from the
configuration's V, K, M and S alone.  Every round it hands its neighbour
the block it sampled (``Vb·K`` int32 counts), the block's id (one int32)
and, under the iteration table lifetime the ring cells pin, the block's
packed word table (``[3, Vb, K]`` int32); an iteration has ``B = S·M``
rounds and ``Vb = ⌈V / B⌉``.  Parked blocks never travel."""

CELL = 4


def bytes_per_iteration(vocab_size: int, num_topics: int, num_workers: int,
                        blocks_per_worker: int) -> int:
    b = num_workers * blocks_per_worker
    vb = -(-vocab_size // b)
    return b * (CELL * vb * num_topics * (1 + 3) + CELL)


def bytes_moved(counts: dict) -> float:
    """``counts``: the window's ``iterations`` and the cell's geometry."""
    return counts["iterations"] * bytes_per_iteration(
        int(counts["vocab_size"]), int(counts["num_topics"]),
        int(counts["num_workers"]), int(counts["blocks_per_worker"]))
