"""Device seconds per round in the flash attention kernels, every pass
(``flash_attention_fwd``, ``_dq``, ``_dkv``), leaf operations only,
averaged over the cell's chips (bench/scopes.py)."""

PREFIX = "flash_attention_"


def read(run):
    scopes = getattr(run, "scopes", None)
    if not scopes:
        return None
    times = [t for k, t in scopes["kernels"].items() if k.startswith(PREFIX)]
    return sum(times) / run.rounds if times else None
