"""Blocking reads of device values to the host per round: the program's
``host_syncs`` counter (repro/obs.to_host), traced run with the
program's counters on."""


def read(run):
    counters = (getattr(run, "program", None) or {}).get("counters", {})
    if "host_syncs" not in counters:
        return None
    return counters["host_syncs"] / run.rounds
