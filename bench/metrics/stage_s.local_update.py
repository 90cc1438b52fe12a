"""Seconds per round in the program's ``local_update`` stage (traced run:
the benchmark's span around the stage, ending in block_until_ready on
its outputs)."""


def read(run):
    if not run.traced or "local_update" not in run.spans:
        return None
    return run.spans["local_update"] / run.rounds
