"""Seconds per round in which no operation ran on the device while the
program's ``fl.evaluate`` span was open (its nested ``fl.eval.*`` spans
included), averaged over the cell's chips (bench/scopes.py)."""


def read(run):
    scopes = getattr(run, "scopes", None)
    if not scopes or "fl.evaluate" not in scopes["idle_within"]:
        return None
    return scopes["idle_within"]["fl.evaluate"] / run.rounds
