"""Seconds per round in the program's evaluation stage: the program's
``fl.evaluate`` span (repro/obs.py), traced run with the program's spans
on."""


def read(run):
    spans = (getattr(run, "program", None) or {}).get("spans", {})
    if "fl.evaluate" not in spans:
        return None
    return spans["fl.evaluate"][1] / run.rounds
