"""The one place that knows the program's parameter layout: it turns the
benchmark's weights and adapters (reference layout) into the trees the
program consumes, and the program's adapters back.  A change of the
program's layout fails here, loudly, instead of feeding wrong weights.
"""
from __future__ import annotations

import jax

from bench.references.decoder import LORA_TARGETS


def model_config(config: dict):
    """The program's ModelConfig from the config file's ``program``
    block (its field names, as run)."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["program"])


def params_for(model, ref: dict) -> dict:
    """Reference-layout weights -> the program's parameter tree (the same
    arrays, no copy).  Checked against ``model.init``'s abstract tree."""
    lay = ref["layers"]
    group = {"norm1": lay["norm1"], "norm2": lay["norm2"],
             "attn": {k: lay[k] for k in
                      ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
             "mlp": {k: lay[k] for k in ("w_gate", "w_in", "w_out")
                     if k in lay}}
    out = {"embed": ref["embed"], "final_norm": ref["final_norm"],
           "blocks": (group,), "tail": ()}
    if "pos" in ref:
        out["pos_embed"] = ref["pos"]
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_def, want_def = jax.tree.structure(out), jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"program parameter layout changed:\n{want_def}\n"
                         f"!= benchmark's\n{got_def}")
    for g, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        if g.shape != w.shape or g.dtype != w.dtype:
            raise ValueError(f"weight {g.shape} {g.dtype} where the program "
                             f"has {w.shape} {w.dtype}")
    return out


def _lora_key(path):
    names = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    targets = [n for n in names if n in LORA_TARGETS]
    if len(targets) != 1 or names[-1] not in ("a", "b"):
        raise ValueError(f"unexpected adapter leaf {names}")
    return targets[0], names[-1]


def lora_for(template, ref_lora: dict):
    """Reference-layout adapter -> the program's tree shaped like
    ``template``."""
    def pick(path, leaf):
        t, ab = _lora_key(path)
        v = ref_lora[t][ab]
        if v.shape != leaf.shape or v.dtype != leaf.dtype:
            raise ValueError(f"adapter {t}.{ab} {v.shape} {v.dtype} where "
                             f"the program has {leaf.shape} {leaf.dtype}")
        return v
    return jax.tree_util.tree_map_with_path(pick, template)


def lora_from(program_lora) -> dict:
    """The program's adapter tree -> reference layout."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(program_lora)[0]:
        t, ab = _lora_key(path)
        out.setdefault(t, {})[ab] = leaf
    return out
