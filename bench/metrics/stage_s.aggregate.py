"""Seconds per round in the program's ``aggregate`` stage (traced run:
the benchmark's span around the stage, ending in block_until_ready on
its outputs)."""


def read(run):
    if not run.traced or "aggregate" not in run.spans:
        return None
    return run.spans["aggregate"] / run.rounds
