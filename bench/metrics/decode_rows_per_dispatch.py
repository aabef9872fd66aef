"""Live rows per decode dispatch over the window: the engine's own exact
counters (`EngineMetrics.n_decode_rows / n_decode_batches`)."""
NAME, UNIT, BETTER, SOURCE = "decode_rows_per_dispatch", "rows", "higher", "program_counter"
LAYER = "engine and scheduler"
MOVES = "out_tok_s"


def compute(rec):
    c0, c1 = rec.window.counters0, rec.window.counters1
    batches = c1["n_decode_batches"] - c0["n_decode_batches"]
    if batches <= 0:
        return None
    return (c1["n_decode_rows"] - c0["n_decode_rows"]) / batches
