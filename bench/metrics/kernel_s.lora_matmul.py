"""Device seconds per round in the LoRA matmul kernels, every pass
(``lora_matmul_fwd``, ``_dx``, ``_dw``, ``_panel_grad``), leaf
operations only, averaged over the cell's chips (bench/scopes.py)."""

PREFIX = "lora_matmul_"


def read(run):
    scopes = getattr(run, "scopes", None)
    if not scopes:
        return None
    times = [t for k, t in scopes["kernels"].items() if k.startswith(PREFIX)]
    return sum(times) / run.rounds if times else None
