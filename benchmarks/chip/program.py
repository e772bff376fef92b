"""The system under test, as the benchmark drives it: the program's config
objects built from a configuration file and a traffic file, its parameter
tree filled with the benchmark's weights, its jitted ``make_train_step``,
its token cache and its packed dataset.  A cell on several chips runs the
program's data-parallel mesh, with its sharded state and batch feed.
Everything this module touches of the program is its public training API
under ``src/repro``; what depends on the model's shape is the family's
(``families/<family>.py``).
"""
from __future__ import annotations

import shutil
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic as traffic_mod
from benchmarks.chip.spec import family
from benchmarks.chip.weights import leaf_norms


def model_config(conf: Dict):
    """The program's ``ModelConfig`` of a configuration (its family's)."""
    return family(conf).model_config(conf)


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


def to_program(bp: Dict, cfg, conf: Dict) -> Dict:
    """The benchmark's flat weights as the program's parameter tree (the
    family's mapping: the same arrays, no copies); its structure and shapes
    must equal the program's own ``init_params``."""
    from repro.models import init_params

    tree = family(conf).to_program(bp, cfg)
    want = jax.eval_shape(lambda: init_params(cfg.model, jax.random.PRNGKey(0),
                                              scan_layers=cfg.parallel.scan_layers))
    got = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if jax.tree_util.tree_structure(got) != jax.tree_util.tree_structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree_util.tree_leaves(got),
                                              jax.tree_util.tree_leaves(want))):
        raise ValueError("the program's parameter tree no longer matches the benchmark's "
                         f"layout:\n program {want}\n benchmark {got}")
    return tree


def from_program(tree: Dict, conf: Dict) -> Dict:
    """Inverse of ``to_program``."""
    return family(conf).from_program(tree)


def init_state(cfg, bp: Dict, conf: Dict, mesh=None):
    """The program's train state from the benchmark's weights; on a mesh,
    placed as the program shards it (FSDP rows of the flat state)."""
    from repro.train import init_state as program_init_state

    state = program_init_state(cfg, params=to_program(bp, cfg, conf))
    if mesh is None:
        return state
    from repro.sharding import activate, param_shardings

    with activate(mesh) as rules:
        return jax.device_put(state, param_shardings(state, rules))


def make_mesh(chips: int):
    """The program's data-parallel mesh over the first ``chips`` devices;
    None for one chip, which runs with no mesh."""
    if chips == 1:
        return None
    from repro.launch.mesh import make_mesh as program_mesh

    return program_mesh((chips,), ("data",))


def make_step(cfg, wrap: Optional[Callable] = None, mesh=None, state=None):
    """The timed call: the jitted fresh-stats train step, state donated.
    On a mesh the step takes the placed ``state`` and returns the next one
    placed alike.  ``wrap`` plants a fault in it (tests only)."""
    from repro.train import make_train_step

    step_fn, _ = make_train_step(cfg, mesh=mesh)

    def step(state, batch):
        return step_fn(state, batch, True)

    fn = wrap(step) if wrap else step
    if mesh is None:
        return jax.jit(fn, donate_argnums=0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = (jax.tree_util.tree_map(lambda x: x.sharding, state), NamedSharding(mesh, P()))
    return jax.jit(fn, donate_argnums=0, out_shardings=out)


def feed(ds, mesh=None):
    """The program's prefetching batch feed, two batches ahead: placed on
    the device, or on a mesh split by rows over its data axis, inside the
    producer thread."""
    if mesh is None:
        return ds.iter_batches(device=True, prefetch_size=2)
    from repro.data.pipeline import device_prefetch

    return device_prefetch(ds.iter_batches(), size=2, mesh=mesh)


def replicate(tree, mesh):
    """``tree`` on every device of ``mesh`` (as is without one)."""
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(tree, NamedSharding(mesh, P()))


def first_step_readings(cfg, conf: Dict):
    """jitted state -> per-tensor norms of the first step's gradient g as
    the optimizer got it (``grad``) and of its GSNR r (``gsnr``), worked out
    from the optimizer's state after it: then the GSNR momentum is
    p = (1 - b3) * r and m = (1 - b1) * r * g, with r >= gamma."""
    b1, b3 = float(cfg.optimizer.b1), float(cfg.optimizer.b3)

    def tree(x):
        return from_program(x.unpack() if hasattr(x, "unpack") else x, conf)

    def run(state):
        m, p = tree(state.opt_state["m"]), tree(state.opt_state["p"])
        return {"grad": leaf_norms({k: m[k] * (1.0 - b3) / ((1.0 - b1) * p[k]) for k in m}),
                "gsnr": leaf_norms({k: v / (1.0 - b3) for k, v in p.items()})}

    return jax.jit(run)


def change_norms(params_tree, bp0: Dict, conf: Dict):
    return jax.jit(lambda t, b: leaf_norms(
        {k: v - b[k] for k, v in from_program(t, conf).items()}))(params_tree, bp0)


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


def batch_shapes(traffic: Dict, mesh=None) -> Dict:
    """The batch the dataset serves (``repro.data.pack_index.gather_rows``),
    on a mesh split by rows as ``feed`` places it."""
    shape = (int(traffic["rows"]), int(traffic["seq_len"]))
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("data", None))
    return {k: jax.ShapeDtypeStruct(shape, jnp.float32 if k == "mask" else jnp.int32,
                                    sharding=sharding)
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
