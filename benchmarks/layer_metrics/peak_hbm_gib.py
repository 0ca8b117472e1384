"""``memory_stats()`` of the fullest chip after the window, in GiB:
``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved`` (what the TPU
runtime sets aside for the loaded programs' temporaries, which the
first does not count).  It bounds the batch a chip can take."""

LAYER = "Sharded step"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_s_chip"


def read(run: dict):
    peak = run.get("device", {}).get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
