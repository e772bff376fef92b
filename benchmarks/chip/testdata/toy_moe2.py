"""A toy model family, for the tests only: the dense family's block with its
MLP replaced by two experts that every token uses, weighted by a softmax
router.  In the program that is its MoE layer with ``top_k`` equal to the
number of experts, a capacity that drops no token and no router losses.
The harness takes it as one more file under ``families/``."""
from __future__ import annotations

import pathlib
from typing import Dict

import jax

from benchmarks.chip import reference as ref
from benchmarks.chip.spec import load_family

dense = load_family(str(pathlib.Path(__file__).with_name("dense.py")))
EXPERTS = 2
WEIGHTS = ("router", "expert_wi", "expert_wg", "expert_wd")

pairs = dense.pairs
attention_fwd = dense.attention_fwd
attention_bwd = dense.attention_bwd


def leaf_shapes(conf: Dict):
    n = dense.dims(conf)
    L, D, F, E = n["L"], n["D"], n["F"], EXPERTS
    s = {k: v for k, v in dense.leaf_shapes(conf).items()
         if k not in ("layers.wi", "layers.wg", "layers.wd")}
    s.update({"layers.router": (L, D, E), "layers.expert_wi": (L, E, D, F),
              "layers.expert_wd": (L, E, F, D)})
    if conf["mlp"] == "gated":
        s["layers.expert_wg"] = (L, E, D, F)
    return s


def model_config(conf: Dict):
    from repro.configs import ModelConfig
    from repro.configs.base import MoEConfig

    moe = MoEConfig(n_experts=EXPERTS, top_k=EXPERTS, capacity_factor=1.0,
                    router_aux_weight=0.0, router_z_weight=0.0)
    return ModelConfig(**dict(dense.model_fields(conf), family="moe", moe=moe))


def to_program(bp: Dict, cfg) -> Dict:
    tree = dense.to_program(bp, cfg)
    layer = tree["groups"]["pos0"]
    del layer["mlp"]
    layer["moe"] = {w: bp[f"layers.{w}"] for w in WEIGHTS if f"layers.{w}" in bp}
    return tree


def from_program(tree: Dict) -> Dict:
    layer = dict(tree["groups"]["pos0"])
    moe = layer.pop("moe")
    bp = dense.from_program(dict(tree, groups={"pos0": dict(layer, mlp={})}))
    bp.update({f"layers.{w}": x for w, x in moe.items()})
    return bp


def experts(conf: Dict, p: Dict, h, quant: bool):
    gate = jax.nn.softmax(ref.mm("bsd,de->bse", h, p["router"], quant), axis=-1)
    out = 0.0
    for e in range(EXPERTS):
        pe = {w: p[f"expert_{w}"][e] for w in ("wi", "wg", "wd") if f"expert_{w}" in p}
        out = out + gate[..., e:e + 1] * dense.mlp(conf, pe, h, quant)
    return out


def loss(conf: Dict, params: Dict, mb: Dict, quant: bool = False):
    return dense.stack_loss(conf, params, mb, quant, experts)


def matmul_params(conf: Dict) -> int:
    n = dense.dims(conf)
    mlp_w = (3 if conf["mlp"] == "gated" else 2) * n["D"] * n["F"]
    return dense.matmul_params(conf) + n["L"] * ((EXPERTS - 1) * mlp_w + n["D"] * EXPERTS)


def model_flops(conf: Dict, live_tokens: int, pairs: int) -> float:
    n = dense.dims(conf)
    return 6.0 * matmul_params(conf) * live_tokens + 12.0 * n["L"] * n["H"] * n["hd"] * pairs
