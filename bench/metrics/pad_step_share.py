"""Percent of the local steps stacked for the clients' programs that are
padding (a client with fewer steps than the longest repeats its last
batch, masked): 1 - steps_valid / steps_stacked, the program's counters
of core/fed_spmd.stack_client_batches."""


def read(run):
    counters = (getattr(run, "program", None) or {}).get("counters", {})
    if not counters.get("steps_stacked"):
        return None
    return 100.0 * (1.0 - counters["steps_valid"]
                    / counters["steps_stacked"])
