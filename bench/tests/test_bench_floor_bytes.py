"""The floor-byte count of a pass, against hand counts."""

from bench.roofline import pass_floor_bytes, triplets


def test_floor_bytes_by_hand():
    # n = 4: triplets 012 013 023 123; 3 duals x 4 B read + written = 24 B
    # each; X and W 16 cells x 4 B, each read or written twice = 16 B each.
    assert triplets(4) == 4
    assert pass_floor_bytes(4) == 4 * 24 + 16 * 16 == 352
    assert pass_floor_bytes(3) == 1 * 24 + 9 * 16 == 168


def test_floor_bytes_at_cell_size():
    assert pass_floor_bytes(768) == 1_814_304_768
