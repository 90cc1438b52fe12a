"""The comparison that decides ``correct``.

Both sides are given as the same record of what the first round
produced (reference layout, numpy):

    client_loss   (C,)  each client's mean local training loss
    client_lora   [C]   each client's adapter after its local update
    global_lora         the aggregated adapter after the round
    eval_loss           mean test loss of the aggregated adapter
    ledger_bytes        wire bytes the round recorded
    feed_bad_rows       rows fed to local training that are not distinct
                        rows of that client's own shard (program side)

The adapters are compared leaf by leaf, a leaf being one layer's A or B
of one target: the gap between the norm of the program's change and the
norm of the reference's, over the larger of the reference leaf's change
norm and the median leaf's.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left
out.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

NOUGHT_GRAD = 1e-3        # of the median leaf's gradient norm


def _leaf_norms(delta: Dict) -> Dict:
    """{(target, ab, layer): ||delta||} over the stacked layer axis."""
    out = {}
    for t in sorted(delta):
        for ab in ("a", "b"):
            x = np.asarray(delta[t][ab], np.float64)
            n = np.sqrt((x.reshape(x.shape[0], -1) ** 2).sum(1))
            for layer, v in enumerate(n):
                out[(t, ab, layer)] = float(v)
    return out


def _sub(a: Dict, b: Dict) -> Dict:
    return {t: {ab: np.asarray(a[t][ab], np.float64)
                - np.asarray(b[t][ab], np.float64) for ab in a[t]}
            for t in a}


def leaf_gaps(prog: Dict, ref: Dict, init: Dict, grad_norm: Dict) -> Dict:
    """{(target, ab, layer): gap} over the leaves that count (see the
    module doc)."""
    np_ = _leaf_norms(_sub(prog, init))
    nr = _leaf_norms(_sub(ref, init))
    keys = list(nr)
    if grad_norm is not None:
        gn = _leaf_norms(grad_norm)
        med_g = float(np.median([gn[k] for k in keys]))
        keys = [k for k in keys if gn[k] >= NOUGHT_GRAD * med_g]
    med = float(np.median([nr[k] for k in keys]))
    out = {}
    for k in keys:
        denom = max(nr[k], med)
        gap = abs(np_[k] - nr[k]) / denom if denom > 0 else math.inf
        out[k] = gap if math.isfinite(np_[k]) else math.inf
    return out


def _median(gaps: Dict, ab: str = None) -> float:
    vals = [g for k, g in gaps.items() if ab is None or k[1] == ab]
    return float(np.median(vals)) if vals else 0.0


def _grad_for(grad_norms: Dict) -> Dict:
    """(L,)-per-leaf gradient norms -> the (L, 1) shape _leaf_norms
    reads (a norm of one number is that number)."""
    return {t: {ab: np.asarray(v)[:, None] for ab, v in d.items()}
            for t, d in grad_norms.items()}


def readings(prog: Dict, ref: Dict, init: Dict,
             client_grad_norms: List[Dict]) -> Dict[str, float]:
    """Every number that can be compared, program (or stand-in) against
    reference.  Adapter numbers are the worst leaf (``*_delta_gap``) or
    the median leaf (``*_median``, ``*_a_median``, ``*_b_median``: over
    the A or B leaves), the worst client for client numbers."""
    gns = [_grad_for(g) for g in client_grad_norms]
    mean_gn = {t: {ab: sum(g[t][ab] for g in gns) / len(gns)
                   for ab in gns[0][t]} for t in gns[0]}
    lp = np.asarray(prog["client_loss"], np.float64)
    lr = np.asarray(ref["client_loss"], np.float64)
    clients = [leaf_gaps(p, r, init, g) for p, r, g in
               zip(prog["client_lora"], ref["client_lora"], gns)]
    glob = leaf_gaps(prog["global_lora"], ref["global_lora"], init, mean_gn)
    out = {
        "train_loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "client_delta_gap": max(max(c.values()) for c in clients),
        "client_delta_median": max(_median(c) for c in clients),
        "client_a_median": max(_median(c, "a") for c in clients),
        "client_b_median": max(_median(c, "b") for c in clients),
        "global_delta_gap": max(glob.values()),
        "global_delta_median": _median(glob),
        "eval_loss_gap": abs(prog["eval_loss"] - ref["eval_loss"])
        / abs(ref["eval_loss"]),
        "ledger_bytes_gap": float(abs(prog["ledger_bytes"]
                                      - ref["ledger_bytes"])),
        "feed_bad_rows": float(prog.get("feed_bad_rows", 0)),
    }
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def judge(values: Dict[str, float], limits: Dict) -> Dict:
    """{name: {"value", "limit"}} for every number the cell's limits
    file names (its entries with a ``limit``)."""
    checks = {}
    for name, entry in limits.items():
        if isinstance(entry, dict) and "limit" in entry:
            checks[name] = {"value": values[name],
                            "limit": float(entry["limit"])}
    if not checks:
        raise KeyError("the limits file names no number")
    return checks


def all_pass(checks: Dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
