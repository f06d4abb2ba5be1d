"""Model step programs, block-sparse attention layers: the pool blocks the
window's token-generation steps READ over the blocks visible to them (what a
dense read would have taken): ``StepRecord.sparse_blocks_read`` over
``StepRecord.sparse_blocks_live``, both counted inside the token-generation
program over rows, KV heads and sparse layers and returned beside the tokens
(batch-padding rows are in both), summed over the window's decode steps. %.
100 while every row is under ``dense_len``; ``topk`` blocks over a row's live
blocks past it. Nothing to read where the program returns no such counts (a
model without block selection)."""


def read(run):
    read_n = live_n = 0
    for r in run.steps:
        if r.decode is None or getattr(r, "sparse_blocks_live", None) is None:
            continue
        read_n += r.sparse_blocks_read
        live_n += r.sparse_blocks_live
    return 100.0 * read_n / live_n if live_n else None
