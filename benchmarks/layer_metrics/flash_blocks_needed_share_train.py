"""Of the blocks of the flash kernels' grid that causality leaves, the share
the kernels' predicate keeps: ``flash_blocks_needed`` over
``flash_blocks_reachable`` of the window's ``metrics_file`` rows (counts the
train step makes once a step from its batch's segment ids, with the range
arithmetic the kernels' operands are made with). 100 on rows without the
counter: a program that does not count predicates a block off on causality
alone, so it computes every reachable block."""
LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    rows = [r for r in run["rows"] if r.get("flash_blocks_reachable")]
    if not rows:
        return 100.0
    return (100.0 * sum(r["flash_blocks_needed"] for r in rows)
            / sum(r["flash_blocks_reachable"] for r in rows))
