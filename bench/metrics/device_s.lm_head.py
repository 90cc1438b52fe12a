"""Device seconds per round in operations under the model step's
``lm_head`` scope (the head product and the loss and accuracy read from
its logits, forward and backward), leaf operations only, averaged over
the cell's chips (bench/scopes.py)."""


def read(run):
    scopes = getattr(run, "scopes", None)
    if not scopes or "lm_head" not in scopes["scopes"]:
        return None
    return scopes["scopes"]["lm_head"] / run.rounds
