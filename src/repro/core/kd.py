"""KD-FedLLMs — logit-based knowledge sharing (paper SSII.B):

    b1 client: local fine-tuning on private data
    b2 client: logits on the PUBLIC dataset with the fine-tuned model
    b3 clients -> server: logits (optionally top-k / int8 compressed)
    b4 server: knowledge processing (weighted/filtered aggregation)
    b5 server: distillation -> global model update
    b6 server: global logits on the public dataset
    b7 server -> clients: global logits
    b8 client: local KD against the global knowledge

No parameters cross the network — communication scales with
|public dataset| x logit dim (paper SSIII.B), which is why this framework
wins for classification and loses for generative tasks.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FedConfig
from repro.core import compression, metrics
from repro.data.loader import epoch_batches


def client_logits(fns, base, lt, public: Dict, batch_size: int = 64):
    """b2: knowledge representations on the public dataset, row i holding
    the logits of public sample i.  Batches arrive permuted (seed-0
    shuffle), so the concatenation is scattered back to original row
    order — distill() indexes teachers by original row id.  Stays on
    device end-to-end (the scatter is a jnp gather-free ``.at[].set``),
    so the b3 compression that follows never syncs through the host."""
    outs = []
    for batch in epoch_batches(public, batch_size, seed=0,
                               drop_remainder=False):
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        outs.append(fns["logits_fn"](base, lt, jb))
    stacked = jnp.concatenate(outs, axis=0)
    perm = jnp.asarray(_epoch_perm(len(public["tokens"]), 0))
    return jnp.zeros_like(stacked).at[perm].set(stacked)


def compress_for_wire(logits, fed: FedConfig):
    """b3 compression (SSIV.B.2 features).  Returns (logits', wire_bytes).

    Pure device path: with both ``logit_topk`` and ``logit_quant_bits``
    set, selection + quantization run as ONE fused Pallas kernel
    (kernels/quantize.topk_quantize_rows); no ``np.asarray`` anywhere, so
    the KD round loop performs zero host transfers for logit upload."""
    x = jnp.asarray(logits)
    if fed.logit_topk and fed.logit_topk < x.shape[-1]:
        if fed.logit_quant_bits:
            comp, wire = compression.topk_quantize(x, fed.logit_topk,
                                                   fed.logit_quant_bits)
            return compression.topk_dequantize(comp), wire
        comp, wire = compression.topk_compress(x, fed.logit_topk)
        return compression.topk_decompress(comp), wire
    if fed.logit_quant_bits:
        return compression.quant_roundtrip(x, fed.logit_quant_bits)
    return x, x.size * 4


def logit_wire_bytes(shape, fed: FedConfig) -> int:
    """Arithmetic twin of ``compress_for_wire``'s byte accounting for a
    logit tensor of ``shape`` — use when only the ledger entry is needed
    (e.g. the b7 download of already-produced global logits) so no
    compression pipeline runs just to be discarded."""
    n, d = math.prod(shape[:-1]), shape[-1]
    topk = fed.logit_topk if (fed.logit_topk and fed.logit_topk < d) else 0
    return metrics.logit_bytes(n, d, topk, fed.logit_quant_bits)


def aggregate_knowledge(client_logits_list: List,
                        weights: Optional[List[float]] = None,
                        entropy_filter_frac: float = 0.0) -> jax.Array:
    """b4: refined global knowledge.  Weighted mean of client logits, with
    optional entropy-based filtering (SSIV.B.3): samples whose mean
    predictive entropy is in the highest ``frac`` quantile are replaced by
    the lowest-entropy client's logits (most-confident knowledge wins).
    jnp end-to-end, so the b3 -> b4 chain stays on the accelerator."""
    if weights is None:
        weights = [1.0] * len(client_logits_list)
    w = jnp.asarray(weights, jnp.float32)
    w = _normalized_w(w)
    stack = jnp.stack([jnp.asarray(x) for x in client_logits_list])
    agg = jnp.einsum("c,cnd->nd", w,
                     stack.astype(jnp.float32)).astype(jnp.float32)
    if entropy_filter_frac > 0.0:
        ent = _entropy_jnp(stack)                          # (C, N)
        mean_ent = ent.mean(axis=0)
        thresh = jnp.quantile(mean_ent, 1.0 - entropy_filter_frac)
        noisy = mean_ent >= thresh
        best_client = ent.argmin(axis=0)                   # (N,)
        chosen = stack[best_client, jnp.arange(stack.shape[1])]
        agg = jnp.where(noisy[:, None], chosen, agg)
    return agg


def aggregate_knowledge_batched(stacked, weights) -> jax.Array:
    """b4 as a client-axis reduction for the SPMD backend: weighted mean
    over axis 0 of a (C, N, D) logit stack in fp32 — lowers to one
    all-reduce when the client axis is sharded over pods."""
    w = jnp.asarray(weights, jnp.float32)
    w = _normalized_w(w)
    return jnp.einsum("c,cnd->nd", w, jnp.asarray(stacked, jnp.float32))


def _normalized_w(w: jax.Array) -> jax.Array:
    """Normalize knowledge weights; a zero-mass cohort (every client
    dropped/quarantined) degrades to a uniform mean instead of NaN.
    Bit-transparent for positive totals."""
    s = w.sum()
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0),
                     1.0 / w.shape[0])


def _entropy_jnp(logits) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(jnp.exp(logp) * logp).sum(axis=-1)


def distill(fns, base, lt, opt_state, public: Dict, teacher: np.ndarray,
            epochs: int, batch_size: int = 64, seed: int = 0):
    """b5/b8: update LoRA params by distilling ``teacher`` logits."""
    rng = jax.random.PRNGKey(seed)
    loss = 0.0
    n = 0
    for ep in range(epochs):
        start = 0
        for batch in epoch_batches(public, batch_size, seed=ep,
                                   drop_remainder=False):
            # teacher rows must follow the same permutation
            sel = _epoch_perm(len(public["tokens"]), ep)[
                start:start + len(batch["tokens"])]
            start += len(batch["tokens"])
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            t = jnp.asarray(teacher[sel])
            rng, sub = jax.random.split(rng)
            lt, opt_state, l = fns["kd_step"](base, lt, opt_state, jb, t,
                                              sub)
            loss += float(obs.to_host(l)) * len(batch["tokens"])
            n += len(batch["tokens"])
    return lt, opt_state, loss / max(n, 1)


def _epoch_perm(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).permutation(n)


# --------------------------------------------------------------------------- #
# Public-dataset alignment (SSIV.B.1 — beyond-paper feature)
# --------------------------------------------------------------------------- #
def align_public_dataset(public: Dict, client_label_hists: List[np.ndarray],
                         target_size: int, seed: int = 0) -> Dict:
    """Importance-resample the public dataset toward the clients' average
    label distribution, using only the lightweight histograms clients
    share (no raw data crosses the network)."""
    rng = np.random.default_rng(seed)
    target = np.mean(np.stack(client_label_hists), axis=0)
    labels = public["labels"]
    pub_hist = np.bincount(labels, minlength=len(target)).astype(np.float64)
    pub_hist /= max(pub_hist.sum(), 1.0)
    w = target[labels] / np.maximum(pub_hist[labels], 1e-9)
    w /= w.sum()
    sel = rng.choice(len(labels), size=target_size, replace=True, p=w)
    return {k: v[sel] for k, v in public.items()}
