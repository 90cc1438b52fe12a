"""The round's share of the chips' bf16 peak, in percent: operations the
round requires (bench/flops.py, real tokens only, the head at the rows
and columns the task reads, no recomputation) over round seconds x
chips x peak."""


def read(run):
    if not run.traced or not run.flops:
        return None
    per_round = sum(run.flops) / len(run.flops)
    return 100.0 * per_round / (run.round_s * run.chips * run.peak_flops)
