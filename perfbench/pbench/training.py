"""Drive the program's ``Trainer`` with the cell's batches.

Set-up builds one ``Trainer`` with its state from the seed and drives it
through its first ``checked_steps`` steps by its own ``train`` call and
feed, reading what the check compares: each step's loss, the norm of each
leaf's first gradient as AdamW got it (its first moment after one step,
over ``1 - b1``), and the norm of each leaf's change after the last of
those steps.  The window then goes on with the same object, one step per
``train`` call, until ``--seconds`` have passed.

The batches are the benchmark's own: token ids drawn from the seed, every
row different, the same for the program and the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from pbench import serving

_TOKENS = 7
#: which of the reference's tensors each of the program's leaves holds,
#: keyed by the leaf's last two path names (or its only one); a packed or
#: zero-padded leaf has the norm of the tensors it packs
LEAVES = {"embed": ("embed",), "final_norm": ("final_norm",),
          "mixer/wqkv": ("wq", "wk", "wv"), "mixer/wo": ("wo",),
          "mixer/norm": ("attn_norm",), "ffn/w1": ("w1",), "ffn/w3": ("w3",), "ffn/w2": ("w2",),
          "ffn/norm": ("ffn_norm",)}
#: faults planted for setting limits and in the tests (never in the
#: benchmark's own runs)
FAULTS = ("frozen", "half_batch", "no_exchange")


def batch(mix: Dict, seed: int, step: int, vocab: int) -> Dict:
    """Step ``step``'s batch: ``global_batch`` rows of ``seq_len + 1``
    uniform token ids, the inputs and their next tokens."""
    b, s = int(mix["global_batch"]), int(mix["seq_len"])
    rng = np.random.default_rng([abs(int(seed)), _TOKENS, step])
    toks = rng.integers(0, vocab, size=(b, s + 1), dtype=np.int64)
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def leaf_name(path) -> str:
    names = [str(getattr(k, "key", getattr(k, "name", ""))) for k in path]
    names = [n for n in names if n and not n.isdigit()]
    return "/".join(names[-2:]) if "/".join(names[-2:]) in LEAVES \
        else names[-1]


def leaf_norms(tree) -> Dict[str, float]:
    """Each leaf's float32 norm, by ``leaf_name``."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    vals = jax.jit(lambda xs: [jnp.linalg.norm(x.astype(jnp.float32))
                               for x in xs])([x for _, x in flat])
    return {leaf_name(p): float(v) for (p, _), v in zip(flat, vals)}


def change_norms(new, old) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(new)
    vals = jax.jit(lambda a, b: [
        jnp.linalg.norm(x.astype(jnp.float32) - y.astype(jnp.float32))
        for x, y in zip(a, b)])([x for _, x in flat], jax.tree.leaves(old))
    return {leaf_name(p): float(v) for (p, _), v in zip(flat, vals)}


@dataclass
class Step:
    t0: float
    t1: float


@dataclass
class TrainRun:
    trainer: object
    params: object
    opt: object
    mix: Dict
    seed: int
    readings: Dict = field(default_factory=dict)
    steps: List[Step] = field(default_factory=list)
    tracing: bool = False

    def span(self, name: str):
        return serving.Recorder(tracing=self.tracing).span(name)

    @property
    def tokens_per_step(self) -> int:
        return int(self.mix["global_batch"]) * int(self.mix["seq_len"])


@contextmanager
def _no_psum():
    """Every ``lax.psum`` traced inside leaves its operand as it is: the
    exchange between chips left out."""
    import jax
    real = jax.lax.psum
    jax.lax.psum = lambda x, *a, **k: x
    try:
        yield
    finally:
        jax.lax.psum = real


def build(cell, seed: int, fault: Optional[str] = None) -> TrainRun:
    """The trainer and its state, driven through the checked steps."""
    import jax

    from repro.configs.base import ParallelConfig
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.trainer import TrainConfig, Trainer
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    mix, conf = cell.traffic, cell.config["train"]
    cfg = serving.model_config(cell)
    mesh = make_mesh(1, 1, cell.chips)
    par = ParallelConfig(tp=cell.chips, dp=1, overlap_mode=mix["overlap_mode"])
    opt = conf["adamw"]
    # the step reads total_steps when it is traced, at its first call: it
    # keeps this config, whose schedule stays in its stable phase for every
    # step a run makes, and the loop gets a copy whose total_steps each
    # train() call raises
    tc = TrainConfig(total_steps=10 ** 9, warmup_steps=conf["warmup_steps"],
                     base_lr=opt["lr"], schedule=conf["schedule"],
                     seed=seed, log_every=10 ** 9, max_retries=0)
    oc = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"],
                     grad_clip=opt["grad_clip"],
                     moment_dtype=conf["moment_dtype"])
    tr = Trainer(cfg, par, mesh, tc, oc)
    tr.tc = dataclasses.replace(tc)
    tr.data_cfg = DataConfig(vocab_size=cfg.vocab_size,
                             seq_len=int(mix["seq_len"]),
                             global_batch=int(mix["global_batch"]), seed=seed)
    vocab = cfg.vocab_size

    def data(step: int) -> Dict:
        b = batch(mix, seed, step, vocab)
        if fault == "half_batch":
            b["labels"] = b["labels"].copy()
            b["labels"][len(b["labels"]) // 2:] = -1
        return b
    tr._data = data
    if fault == "frozen":
        real = tr.step_fn

        def frozen(params, opt_state, b, step):
            copy = jax.jit(lambda t: jax.tree.map(lambda x: x + 0, t))
            _, _, metrics = real(copy(params), copy(opt_state), b, step)
            return params, opt_state, metrics
        tr.step_fn = frozen
    params, opt_state = tr.init_state()
    run = TrainRun(tr, params, opt_state, mix, seed)
    n = int(mix["checked_steps"])
    losses = []
    with (_no_psum() if fault == "no_exchange" else _nullctx()):
        for upto in (1, n):
            tr.tc.total_steps = upto
            run.params, run.opt, hist = tr.train(run.params, run.opt)
            losses += [h["loss"] for h in hist]
            if upto == 1:
                g = leaf_norms(run.opt["mu"])
                run.readings["grad"] = {k: v / (1.0 - opt["b1"])
                                        for k, v in g.items()}
    p0 = serving.init_params(cfg, par, mesh, seed)
    run.readings["change"] = change_norms(run.params, p0)
    del p0
    run.readings["loss"] = losses
    jax.block_until_ready(run.params)
    return run


@contextmanager
def _nullctx():
    yield


def run_window(run: TrainRun, seconds: float,
               trace_dir: Optional[str] = None, trace_s: float = 0.0
               ) -> "serving.Outcome":
    """One step per ``Trainer.train`` call until the window closes."""
    tr = run.trainer
    ws = time.perf_counter()
    we = ws + seconds
    mid = ws + max(0.0, (seconds - trace_s) / 2)
    tracer = serving.Tracer(run, lambda: None, mid, mid + trace_s, trace_dir)
    compiles = serving._compile_counter()
    n0 = compiles[0]
    while True:
        now = time.perf_counter()
        tracer.poll(now)
        if now >= we:
            break
        tr.tc.total_steps = tr.step + 1
        t0 = time.perf_counter()
        with run.span("bench.train_step"):
            run.params, run.opt, _ = tr.train(run.params, run.opt)
        run.steps.append(Step(t0, time.perf_counter()))
    tracer.stop()
    done = [s for s in run.steps if s.t1 <= we]
    return serving.Outcome((ws, we), done, done,
                           len(done) * run.tokens_per_step, tracer,
                           tr.failures, 0.0, compiles[0] - n0)


def free(run: TrainRun) -> None:
    """Drop the trainer's device state so the reference has the chips."""
    run.params = run.opt = None
    run.trainer = None
    gc.collect()


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers the check holds to limits: the widest relative gap of
    the per-step losses, and, over the leaves, the widest gap between the
    program's norm and the reference's, as a share of the reference's norm
    of that leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out of both (they move by round-off alone)."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
    if len(got["loss"]) != len(ref["loss"]):
        gaps = [float("inf")]
    out = {"loss_gap": max(gaps), "first_loss_gap": gaps[0]}
    ref_g = _grouped(ref["grad"], got["grad"])
    med_g = float(np.median(list(ref_g.values())))
    keep = [k for k, v in ref_g.items() if v >= 1e-3 * med_g]
    for name in ("grad", "change"):
        r = _grouped(ref[name], got[name])
        med = float(np.median([r[k] for k in keep]))
        out[f"{name}_norm_gap"] = max(
            abs(got[name][k] - r[k]) / max(r[k], med) for k in keep)
    return out


def _grouped(ref: Dict[str, float], leaves: Dict[str, float]
             ) -> Dict[str, float]:
    """The reference's norms gathered into the program's leaves."""
    out = {}
    for leaf in leaves:
        if leaf not in LEAVES:
            raise KeyError(f"the program's leaf {leaf!r} has no tensors in "
                           f"the reference (pbench/training.py LEAVES)")
        out[leaf] = float(np.sqrt(sum(ref[t] ** 2 for t in LEAVES[leaf])))
    return out


def reference(cell, seed: int, devices, fp8: bool = False) -> Dict:
    from pbench.manifest import load_reference
    ref = load_reference(cell)
    mix = cell.traffic
    vocab = int(cell.config["model"]["vocab_size"])
    batches = [batch(mix, seed, i, vocab)
               for i in range(int(mix["checked_steps"]))]
    return ref.train(cell.config["model"], seed, batches,
                     cell.config["train"]["adamw"], devices, fp8=fp8)


def verdict(cell, run_readings: Dict, seed: int, devices, failed: int,
            control: bool = False, log: Callable = print):
    """``correct``, the numbers compared with their limits, and what else
    the check read."""
    limits = cell.config["check"]
    ref = reference(cell, seed, devices)
    got = compare(run_readings, ref)
    info = {"loss": run_readings["loss"], "ref_loss": ref["loss"]}
    if control:
        # the float8 reference in the program's place, through the same
        # comparison; the program's own numbers are kept beside it
        info["program"] = got
        ctl = reference(cell, seed, devices, fp8=True)
        got = compare(as_leaves(ctl, run_readings), ref)
    # the numbers with a limit in the configuration are held to it; the
    # others are printed beside them
    checks = {"failed_steps": {"value": failed, "limit": 0}}
    for k, v in got.items():
        if k in limits:
            checks[k] = {"value": v, "limit": limits[k]}
        else:
            info[k] = v
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks, info


def as_leaves(ref: Dict, like: Dict) -> Dict:
    """Reference readings gathered into the program's leaves of ``like``."""
    return {"loss": ref["loss"],
            "grad": _grouped(ref["grad"], like["grad"]),
            "change": _grouped(ref["change"], like["change"])}
