"""The system under test, as the benchmark drives it: the program's config
objects built from a configuration file and a traffic file, its parameter
tree filled with the benchmark's weights, its jitted ``make_train_step``,
its token cache and its packed dataset.  Everything this module touches of
the program is its public training API under ``src/repro``.
"""
from __future__ import annotations

import shutil
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic as traffic_mod
from benchmarks.chip.weights import dims, leaf_norms

# What the program's model computes and no configuration can change: its
# norms' epsilon (repro.models.common.apply_norm), RoPE and no token types,
# no scalar multipliers; attention scaled by 1/sqrt(head_dim).  A
# configuration file that states another value cannot be run as stated.
PROGRAM_FIXED = {"norm_eps": 1e-6, "position_embedding_type": "rope", "type_vocab_size": 0,
                 "embedding_multiplier": 1.0, "residual_multiplier": 1.0, "logits_scaling": 1.0}


def model_config(conf: Dict):
    from repro.configs import ModelConfig

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
    return ModelConfig(
        name=conf.get("name", "chipbench"), family="dense", n_layers=n["L"], d_model=n["D"], n_heads=n["H"],
        n_kv_heads=n["KV"], d_ff=n["F"], vocab_size=n["V"], head_dim=n["hd"],
        block_pattern=("attn",), rope_theta=float(conf["rope_theta"]), norm=conf["norm"],
        act=act, causal=bool(conf["causal"]), tie_embeddings=bool(conf["tie_word_embeddings"]),
    )


def train_config(conf: Dict, traffic: Dict):
    from repro.configs import Config, OptimizerConfig, ParallelismConfig

    opt = dict(traffic["optimizer"])
    opt["k"] = int(traffic["k"])
    return Config(
        model=model_config(conf), optimizer=OptimizerConfig(**opt),
        parallel=ParallelismConfig(param_dtype=conf["param_dtype"],
                                   compute_dtype=conf["compute_dtype"]),
        global_batch=int(traffic["rows"]), seq_len=int(traffic["seq_len"]),
    )


def _norm_tree(bp, prefix):
    out = {"scale": bp[f"{prefix}_scale" if prefix != "final" else "final.scale"]}
    bias = f"{prefix}_bias" if prefix != "final" else "final.bias"
    if bias in bp:
        out["bias"] = bp[bias]
    return out


def to_program(bp: Dict, cfg) -> Dict:
    """The benchmark's flat weights as the program's parameter tree (the same
    arrays, no copies); its structure and shapes must equal the program's
    own ``init_params``."""
    from repro.models import init_params

    layer = {
        "ln1": _norm_tree(bp, "layers.ln1"), "ln2": _norm_tree(bp, "layers.ln2"),
        "attn": {w: bp[f"layers.{w}"] for w in ("wq", "wk", "wv", "wo")},
        "mlp": {w: bp[f"layers.{w}"] for w in ("wi", "wg", "wd") if f"layers.{w}" in bp},
    }
    tree = {"embed": {"embed": bp["embed"]}, "groups": {"pos0": layer}, "tail": [],
            "final_norm": _norm_tree(bp, "final")}
    if "head" in bp:
        tree["head"] = bp["head"]
    want = jax.eval_shape(lambda: init_params(cfg.model, jax.random.PRNGKey(0),
                                              scan_layers=cfg.parallel.scan_layers))
    got = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if jax.tree_util.tree_structure(got) != jax.tree_util.tree_structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree_util.tree_leaves(got),
                                              jax.tree_util.tree_leaves(want))):
        raise ValueError("the program's parameter tree no longer matches the benchmark's "
                         f"layout:\n program {want}\n benchmark {got}")
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


def init_state(cfg, bp: Dict):
    from repro.train import init_state as program_init_state

    return program_init_state(cfg, params=to_program(bp, cfg))


def make_step(cfg, wrap: Optional[Callable] = None):
    """The timed call: the jitted fresh-stats train step, state donated.
    ``wrap`` plants a fault in it (tests only)."""
    from repro.train import make_train_step

    step_fn, _ = make_train_step(cfg)

    def step(state, batch):
        return step_fn(state, batch, True)

    return jax.jit(wrap(step) if wrap else step, donate_argnums=0)


def first_step_readings(cfg):
    """jitted state -> per-tensor norms of the first step's gradient g as
    the optimizer got it (``grad``) and of its GSNR r (``gsnr``), worked out
    from the optimizer's state after it: then the GSNR momentum is
    p = (1 - b3) * r and m = (1 - b1) * r * g, with r >= gamma."""
    b1, b3 = float(cfg.optimizer.b1), float(cfg.optimizer.b3)

    def tree(x):
        return from_program(x.unpack() if hasattr(x, "unpack") else x)

    def run(state):
        m, p = tree(state.opt_state["m"]), tree(state.opt_state["p"])
        return {"grad": leaf_norms({k: m[k] * (1.0 - b3) / ((1.0 - b1) * p[k]) for k in m}),
                "gsnr": leaf_norms({k: v / (1.0 - b3) for k, v in p.items()})}

    return jax.jit(run)


def change_norms(params_tree, bp0: Dict):
    return jax.jit(lambda t, b: leaf_norms(
        {k: v - b[k] for k, v in from_program(t).items()}))(params_tree, bp0)


def write_corpus(traffic: Dict, vocab: int, seed: int, cache_dir) -> None:
    from repro.data import write_token_cache

    shutil.rmtree(cache_dir, ignore_errors=True)
    dtype = np.uint16 if vocab <= np.iinfo(np.uint16).max + 1 else np.int32
    write_token_cache(traffic_mod.corpus(traffic, vocab, seed), str(cache_dir),
                      dtype=dtype, vocab=vocab)


def dataset(cache, traffic: Dict):
    """The program's packed dataset over ``cache``; its epoch order is keyed
    by the traffic's ``layout_seed``, the same for every ``--seed``."""
    from repro.data.memmap import IndexedPackedDataset

    return IndexedPackedDataset(cache, int(traffic["seq_len"]), int(traffic["rows"]),
                                seed=int(traffic["layout_seed"]))


def piece_lengths(ds, lo: int, hi: int) -> np.ndarray:
    """Trained lengths of the document pieces in global rows [lo, hi) of the
    stream ``ds`` serves from its start (rows run on across epochs)."""
    out, epoch, base = [], 0, 0
    while base < hi:
        pack = ds.pack_for(epoch)
        a, b = max(lo - base, 0), min(hi - base, pack.n_rows)
        if a < b:
            out.append(pack.piece_len[int(pack.row_ptr[a]):int(pack.row_ptr[b])])
        base += pack.n_rows
        epoch += 1
    return np.concatenate(out) if out else np.zeros(0, np.int32)


def batch_shapes(traffic: Dict) -> Dict:
    """The batch the dataset serves (``repro.data.pack_index.gather_rows``)."""
    shape = (int(traffic["rows"]), int(traffic["seq_len"]))
    return {k: jax.ShapeDtypeStruct(shape, jnp.float32 if k == "mask" else jnp.int32)
            for k in ("tokens", "targets", "positions", "segments", "mask")}


def host_batches(ds, n: int):
    return [ds.next_batch() for _ in range(n)]


def memory_bytes(compiled) -> int:
    """What the compiled step holds on its device: arguments, temporaries
    and the outputs that do not alias a donated argument."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def compute_itemsize(conf: Dict) -> int:
    return jnp.dtype(conf["compute_dtype"]).itemsize
