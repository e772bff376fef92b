"""The dense family: a stack of identical pre-norm blocks, each
x + attn(norm(x)) then x + mlp(norm(x)), under a token embedding, a final
norm and a tied or separate vocabulary projection.  Bidirectional encoders
(bert-large) and causal GQA decoders (granite-3-2b) are both of it.

Everything of the harness that depends on the model's shape is here, and
the harness finds it by the configuration's ``"family"`` (``spec.family``):

  leaf_shapes      the benchmark's weight layout (``weights.py``)
  model_config     the program's ``ModelConfig``, refusing what it cannot run
  to_program       the benchmark's flat weights as the program's tree, and
  from_program     back (``program.py`` checks the tree against the program)
  loss             the plain float32 loss the reference differentiates, with
                   its float8 control (``reference.py``)
  pairs, matmul_params, model_flops, attention_fwd, attention_bwd
                   the least work of a step (``work.py``)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference as ref
from benchmarks.chip import work

# What the program's model computes and no configuration can change: its
# norms' epsilon (repro.models.common.apply_norm), RoPE and no token types,
# no scalar multipliers; attention scaled by 1/sqrt(head_dim).  A
# configuration file that states another value cannot be run as stated.
PROGRAM_FIXED = {"norm_eps": 1e-6, "position_embedding_type": "rope", "type_vocab_size": 0,
                 "embedding_multiplier": 1.0, "residual_multiplier": 1.0, "logits_scaling": 1.0}


def dims(conf: Dict) -> Dict[str, int]:
    d = int(conf["hidden_size"])
    h = int(conf["num_attention_heads"])
    return {
        "L": int(conf["num_hidden_layers"]), "D": d, "H": h,
        "KV": int(conf["num_key_value_heads"]), "hd": int(conf.get("head_dim") or d // h),
        "F": int(conf["intermediate_size"]), "V": int(conf["vocab_size"]),
    }


def leaf_shapes(conf: Dict) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every parameter tensor."""
    n = dims(conf)
    L, D, F, V = n["L"], n["D"], n["F"], n["V"]
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    layernorm = conf["norm"] == "layernorm"
    s = {"embed": (V, D)}
    for ln in ("ln1", "ln2"):
        s[f"layers.{ln}_scale"] = (L, D)
        if layernorm:
            s[f"layers.{ln}_bias"] = (L, D)
    s.update({"layers.wq": (L, D, q), "layers.wk": (L, D, kv), "layers.wv": (L, D, kv),
              "layers.wo": (L, q, D), "layers.wi": (L, D, F), "layers.wd": (L, F, D)})
    if conf["mlp"] == "gated":
        s["layers.wg"] = (L, D, F)
    s["final.scale"] = (D,)
    if layernorm:
        s["final.bias"] = (D,)
    if not conf["tie_word_embeddings"]:
        s["head"] = (D, V)
    return s


def model_fields(conf: Dict) -> Dict:
    """The ``ModelConfig`` fields of a dense stack, after checking that the
    program computes what the configuration states."""
    n = dims(conf)
    fixed = dict(PROGRAM_FIXED, attention_multiplier=n["hd"] ** -0.5)
    for key, value in fixed.items():
        if key in conf and conf[key] != value:
            raise ValueError(f"{key} {conf[key]!r} cannot be run: the program computes {value!r}")
    act = {("gated", "silu"): "swiglu", ("dense", "gelu_tanh"): "gelu"}.get(
        (conf["mlp"], conf["hidden_act"]))
    if act is None:
        raise ValueError(f"no program activation for mlp {conf['mlp']!r} with "
                         f"{conf['hidden_act']!r}")
    if n["L"] < 2:
        raise ValueError("the program stacks layer tensors only from two layers up")
    return dict(
        name=conf.get("name", "chipbench"), family="dense", n_layers=n["L"], d_model=n["D"],
        n_heads=n["H"], n_kv_heads=n["KV"], d_ff=n["F"], vocab_size=n["V"], head_dim=n["hd"],
        block_pattern=("attn",), rope_theta=float(conf["rope_theta"]), norm=conf["norm"],
        act=act, causal=bool(conf["causal"]), tie_embeddings=bool(conf["tie_word_embeddings"]),
    )


def model_config(conf: Dict):
    from repro.configs import ModelConfig

    return ModelConfig(**model_fields(conf))


def norm_tree(bp, prefix):
    out = {"scale": bp[f"{prefix}_scale" if prefix != "final" else "final.scale"]}
    bias = f"{prefix}_bias" if prefix != "final" else "final.bias"
    if bias in bp:
        out["bias"] = bp[bias]
    return out


def to_program(bp: Dict, cfg) -> Dict:
    """The benchmark's flat weights as the program's parameter tree (the same
    arrays, no copies)."""
    layer = {
        "ln1": norm_tree(bp, "layers.ln1"), "ln2": norm_tree(bp, "layers.ln2"),
        "attn": {w: bp[f"layers.{w}"] for w in ("wq", "wk", "wv", "wo")},
        "mlp": {w: bp[f"layers.{w}"] for w in ("wi", "wg", "wd") if f"layers.{w}" in bp},
    }
    tree = {"embed": {"embed": bp["embed"]}, "groups": {"pos0": layer}, "tail": [],
            "final_norm": norm_tree(bp, "final")}
    if "head" in bp:
        tree["head"] = bp["head"]
    return tree


def from_program(tree: Dict) -> Dict:
    """Inverse of ``to_program``."""
    layer = tree["groups"]["pos0"]
    bp = {"embed": tree["embed"]["embed"], "final.scale": tree["final_norm"]["scale"]}
    if "bias" in tree["final_norm"]:
        bp["final.bias"] = tree["final_norm"]["bias"]
    for ln in ("ln1", "ln2"):
        for part, x in layer[ln].items():
            bp[f"layers.{ln}_{part}"] = x
    for group in ("attn", "mlp"):
        for w, x in layer[group].items():
            bp[f"layers.{w}"] = x
    if "head" in tree:
        bp["head"] = tree["head"]
    return bp


def stacked_layers(params: Dict) -> Dict:
    """The ``layers.*`` tensors by their short names, LayerNorm biases made
    zero where the norm has none."""
    stacked = {k[len("layers."):]: v for k, v in params.items() if k.startswith("layers.")}
    for name in ("ln1_bias", "ln2_bias"):
        stacked.setdefault(name, jnp.zeros_like(stacked["ln1_scale"]))
    return stacked


def attention_block(conf: Dict, p: Dict, x, pos, seg, quant: bool):
    """x + attn(norm(x)) of one layer's tensors ``p``."""
    n = dims(conf)
    kind, eps = conf["norm"], float(conf["norm_eps"])
    b, s = pos.shape
    h = ref.norm(x, p["ln1_scale"], p["ln1_bias"], kind, eps)
    q = ref.mm("bsd,de->bse", h, p["wq"], quant).reshape(b, s, n["H"], n["hd"])
    k = ref.mm("bsd,de->bse", h, p["wk"], quant).reshape(b, s, n["KV"], n["hd"])
    v = ref.mm("bsd,de->bse", h, p["wv"], quant).reshape(b, s, n["KV"], n["hd"])
    theta = float(conf["rope_theta"])
    q, k = ref.rope(q, pos, theta), ref.rope(k, pos, theta)
    a = ref.attention(q, k, v, pos, seg, bool(conf["causal"]), quant)
    return x + ref.mm("bse,ed->bsd", a.reshape(b, s, -1), p["wo"], quant)


def mlp(conf: Dict, p: Dict, h, quant: bool):
    """The MLP of one layer on the normed input ``h``."""
    up = ref.mm("bsd,df->bsf", h, p["wi"], quant)
    if conf["mlp"] == "gated":
        f = jax.nn.silu(ref.mm("bsd,df->bsf", h, p["wg"], quant)) * up
    else:
        f = jax.nn.gelu(up, approximate=True)
    return ref.mm("bsf,fd->bsd", f, p["wd"], quant)


def loss(conf: Dict, params: Dict, mb: Dict, quant: bool = False):
    """Mean cross-entropy over the live tokens of one microbatch."""
    return stack_loss(conf, params, mb, quant, mlp)


def stack_loss(conf: Dict, params: Dict, mb: Dict, quant: bool, ffn):
    """The loss of a stack of pre-norm blocks whose feed-forward part is
    ``ffn(conf, p, h, quant)``, the layers under a scan with
    rematerialization."""
    kind, eps = conf["norm"], float(conf["norm_eps"])
    pos, seg = mb["positions"], mb["segments"]
    x = params["embed"][mb["tokens"]]

    @jax.checkpoint
    def layer(x, p):
        x = attention_block(conf, p, x, pos, seg, quant)
        h = ref.norm(x, p["ln2_scale"], p["ln2_bias"], kind, eps)
        return x + ffn(conf, p, h, quant), None

    x, _ = jax.lax.scan(layer, x, stacked_layers(params))
    x = ref.norm(x, params["final.scale"], params.get("final.bias", 0.0), kind, eps)
    head = params["embed"].T if conf["tie_word_embeddings"] else params["head"]
    return ref.mean_nll(x, head, mb["targets"], mb["mask"], quant)


def pairs(conf: Dict, pieces: np.ndarray) -> int:
    """Live (query, key) pairs of a step's document pieces: every layer
    attends over all of them."""
    return work.live_pairs(pieces, bool(conf["causal"]))


def matmul_params(conf: Dict) -> int:
    """Weights a token multiplies by: per layer the q, k, v, o projections
    and the MLP (two or three matrices); the vocabulary projection once,
    whether tied to the embedding or not.  The embedding gather is no
    matmul."""
    n = dims(conf)
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    mlp_w = (3 if conf["mlp"] == "gated" else 2) * n["D"] * n["F"]
    return n["L"] * (2 * n["D"] * q + 2 * n["D"] * kv + mlp_w) + n["D"] * n["V"]


def model_flops(conf: Dict, live_tokens: int, pairs: int) -> float:
    """Forward and backward, no recompute: 6 FLOPs per matmul weight per
    live token, and 12 * L * H * hd per live pair for the attention scores
    and their weighted sum (4 * hd per head and layer forward, twice that
    backward)."""
    n = dims(conf)
    return 6.0 * matmul_params(conf) * live_tokens + 12.0 * n["L"] * n["H"] * n["hd"] * pairs


def attention_fwd(conf: Dict, live_tokens: int, pairs: int, itemsize: int):
    """(FLOPs, bytes) of the attention forward over all layers: 4 * hd per
    head and live pair; q, k, v read and o written once per live token."""
    n = dims(conf)
    flops = 4.0 * n["hd"] * n["H"] * pairs * n["L"]
    nbytes = float(live_tokens) * n["L"] * (2 * n["H"] + 2 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes


def attention_bwd(conf: Dict, live_tokens: int, pairs: int, itemsize: int):
    """(FLOPs, bytes) of the attention backward over all layers: 8 * hd per
    head and live pair (the scores' and the weighted sum's gradients, no
    recompute); q, k, v, o, do read and dq, dk, dv written once."""
    n = dims(conf)
    flops = 8.0 * n["hd"] * n["H"] * pairs * n["L"]
    nbytes = float(live_tokens) * n["L"] * (4 * n["H"] + 4 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes
