"""Seconds per round of the executor's host input pipeline:
fed_spmd.stack_client_batches, stack_trees and unstack_tree (traced
run, the benchmark's spans around each call)."""

CALLS = ("stack_client_batches", "stack_trees", "unstack_tree")


def read(run):
    if not run.traced or not any(c in run.spans for c in CALLS):
        return None
    return sum(run.spans.get(c, 0.0) for c in CALLS) / run.rounds
