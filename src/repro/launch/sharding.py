"""Sharding policy: PartitionSpec trees for params, LoRA, optimizer
state, batches and KV caches (DESIGN SS5).

Name-based rules with divisibility fallbacks, evaluated at spec-build
time against the actual mesh:

- embeddings / LM head: vocab-dim on ``model`` when divisible, else the
  d_model dim, else replicate.
- attention / MLP projections: column-parallel in, row-parallel out
  (megatron layout); non-divisible dims fall back to the other scheme,
  then to replication (qwen2's 12 heads, whisper's 51865 vocab).
- MoE experts: expert dim on ``model`` when divisible (qwen3-moe 128/16),
  else the per-expert ffn dim (mixtral 8 experts < 16 shards).
- LoRA A follows its base matrix's input sharding, B the output sharding.
- KV caches: batch on data axes, cache sequence dim on ``model``
  (sequence-sharded cache: a 32k x128-batch mistral cache drops from
  94 GiB to 5.9 GiB per device).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# column-parallel (shard output dim) / row-parallel (shard input dim)
COL = {"wq", "wk", "wv", "w_gate", "w_in", "cm_w_k", "w_rec_in",
       "w_gate_in", "w_r", "w_k", "w_v", "w_g", "cm_w_r", "w_down"}
ROW = {"wo", "w_out", "cm_w_v", "w_o", "w_up"}
VEC_COL = {"bq", "bk", "bv", "b_a", "b_x", "lambda", "conv_b"}
REPLICATE = {"router", "decay_a", "decay_b", "img_proj"}


def _div(n: int, m: int) -> bool:
    return n % m == 0


# --------------------------------------------------------------------------- #
# Stacked-client-axis shardings (round engine + fed_round dry-run)
# --------------------------------------------------------------------------- #
def client_spec(mesh, ndim: int) -> P:
    """PartitionSpec putting a leading stacked-client axis on the mesh's
    client axes (launch/mesh.client_axes) and replicating the rest."""
    from repro.launch.mesh import client_axes
    return P(client_axes(mesh), *([None] * (ndim - 1)))


def client_shardings(mesh, tree):
    """Mirror-structured NamedSharding tree for client-stacked arrays
    (leaves have the client dimension leading)."""
    return jax.tree.map(
        lambda x: NamedSharding(mesh, client_spec(mesh, x.ndim)), tree)


def shard_client_tree(mesh, tree):
    """Place a client-stacked tree with explicit client-axis
    NamedShardings.  Every leaf's stack size must be a multiple of the
    client-axis extent: a stack left off the mesh would meet mesh-placed
    operands in the same jitted call, so a mismatch is an error, never a
    skip."""
    from repro.launch.mesh import client_axis_size
    n = client_axis_size(mesh)
    bad = {x.shape[0] for x in jax.tree.leaves(tree) if x.shape[0] % n}
    if bad:
        raise ValueError(f"client stack of {sorted(bad)} is not a multiple "
                         f"of the mesh's client axis ({n} devices)")
    return jax.device_put(tree, client_shardings(mesh, tree))


def replicate_tree(mesh, tree):
    """Place a tree every client shares (base weights, public batches)
    whole on every device of ``mesh``."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


class ShardingPolicy:
    def __init__(self, mesh, cfg):
        self.mesh = mesh
        self.cfg = cfg
        self.M = mesh.shape["model"]
        self.dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        self.dp_size = 1
        for a in self.dp:
            self.dp_size *= mesh.shape[a]

    # ------------------------------------------------------------------ #
    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _pad(self, spec_tail, ndim):
        return P(*([None] * (ndim - len(spec_tail)) + list(spec_tail)))

    # ------------------------------------------------------------------ #
    def param_spec(self, path, leaf) -> P:
        name = path[-1]
        shape = leaf.shape
        nd = leaf.ndim
        M = self.M
        if nd == 0 or name.startswith("mu_") or name in (
                "scale", "bias", "ln_x", "bonus_u", "decay_w0"):
            return P()
        if name == "embed":
            V, d = shape[-2], shape[-1]
            if _div(V, M):
                return self._pad([("model"), None], nd)
            if _div(d, M):
                return self._pad([None, "model"], nd)
            return P()
        if name == "pos_embed":
            return P()
        if name == "lm_head":
            d, V = shape[-2], shape[-1]
            if _div(V, M):
                return self._pad([None, "model"], nd)
            if _div(d, M):
                return self._pad(["model", None], nd)
            return P()
        # MoE expert tensors: (.., E, d_in, d_out)
        is_expert = self.cfg.is_moe and name in (
            "w_gate", "w_in", "w_out") and nd >= 3 and \
            shape[-3] == self.cfg.n_experts
        if is_expert:
            E = shape[-3]
            if _div(E, M):
                return self._pad(["model", None, None], nd)
            # fall back: shard the per-expert ffn dim
            io = -1 if name in ("w_gate", "w_in") else -2
            if _div(shape[io], M):
                tail = [None, None, None]
                tail[io] = "model"
                return self._pad(tail, nd)
            return P()
        if name in REPLICATE:
            return P()
        if name == "conv_w":                       # (K, w)
            if _div(shape[-1], M):
                return self._pad([None, "model"], nd)
            return P()
        if name in ("w_a", "w_x"):                 # (w, w) lru gates
            if _div(shape[-1], M):
                return self._pad([None, "model"], nd)
            return P()
        if name in VEC_COL:
            if _div(shape[-1], M):
                return self._pad(["model"], nd)
            return P()
        if name in COL:
            if _div(shape[-1], M):
                return self._pad([None, "model"], nd)
            if _div(shape[-2], M):
                return self._pad(["model", None], nd)
            return P()
        if name in ROW:
            if _div(shape[-2], M):
                return self._pad(["model", None], nd)
            if _div(shape[-1], M):
                return self._pad([None, "model"], nd)
            return P()
        return P()

    # ------------------------------------------------------------------ #
    def lora_spec(self, base_path, which: str, leaf) -> P:
        """A follows base input dim; B follows base output dim."""
        name = base_path[-1]
        nd = leaf.ndim
        M = self.M
        col = name in COL or name in ("embed", "lm_head")
        if which == "a":
            if not col and _div(leaf.shape[-2], M):
                return self._pad(["model", None], nd)    # row-parallel base
            return P()
        if col and _div(leaf.shape[-1], M):
            return self._pad([None, "model"], nd)
        return P()

    # ------------------------------------------------------------------ #
    def tree_specs(self, params) -> object:
        """Mirror-structured PartitionSpec tree (params or bound trees)."""

        def rec(t, path):
            if isinstance(t, dict):
                if set(t) == {"a", "b"} and hasattr(t["a"], "ndim"):
                    return {"a": self.lora_spec(path, "a", t["a"]),
                            "b": self.lora_spec(path, "b", t["b"])}
                return {k: rec(v, path + (k,)) for k, v in t.items()}
            if isinstance(t, (tuple, list)):
                return tuple(rec(v, path) for v in t)
            if t is None:
                return None
            return self.param_spec(path, t)

        return rec(params, ())

    def tree_shardings(self, params):
        return jax.tree.map(
            lambda s: self.named(s),
            self.tree_specs(params),
            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------ #
    def opt_specs(self, lora_specs):
        """Adam state mirrors its params; step scalar replicated."""
        return {"m": lora_specs, "v": lora_specs, "step": P()}

    # ------------------------------------------------------------------ #
    def batch_spec(self, batch_shapes, shardable_batch: bool = True) -> dict:
        dp = self.dp if shardable_batch else ()
        out = {}
        for k, v in batch_shapes.items():
            lead = dp if (shardable_batch
                          and _div(v.shape[0], max(self.dp_size, 1))) else ()
            out[k] = P(lead, *([None] * (v.ndim - 1))) if lead else P(
                *([None] * v.ndim))
        return out

    # ------------------------------------------------------------------ #
    def cache_spec(self, path, leaf) -> P:
        """KV caches: batch on data axes, cache seq dim on model."""
        name = path[-1]
        nd = leaf.ndim
        shape = leaf.shape
        # attention kv caches: (..., B, S_cache, KV, hd).  Sequence-shard
        # only LARGE caches: ring buffers (sliding windows <= 4k) are small
        # and a model-sharded seq dim makes every decode update/read
        # all-gather the full cache (SSPerf hillclimb 2: mixtral decode
        # dropped 470 MB -> ~0 all-gather per layer).
        if name in ("k", "v") and nd >= 4:
            spec = [None] * nd
            if _div(shape[-4], self.dp_size):
                spec[-4] = self.dp
            if shape[-3] >= 16384 and _div(shape[-3], self.M):
                spec[-3] = "model"
            return P(*spec)
        # recurrent states: (..., B, ...) — batch after optional group dim
        b_ax = nd - 2 if name in ("h", "x_tm", "x_cm") else None
        spec = [None] * nd
        for ax in range(nd):
            if leaf.shape[ax] >= self.dp_size and _div(
                    leaf.shape[ax], self.dp_size):
                spec[ax] = self.dp
                break
        return P(*spec)

    def cache_shardings(self, cache_shapes):
        def rec(t, path):
            if isinstance(t, dict):
                return {k: rec(v, path + (k,)) for k, v in t.items()}
            if isinstance(t, (tuple, list)):
                return tuple(rec(v, path) for v in t)
            return self.named(self.cache_spec(path, t))
        return rec(cache_shapes, ())
