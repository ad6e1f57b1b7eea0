"""Driver of the profiled training job: the profiling-cost path.

Traffic keys (``traffic/<name>.json``): ``sample_every`` (ScalAna's K),
``setup_steps`` (the steps set-up drives and the reference follows), and
``optimizer`` (the program's AdamW and schedule constants, which the
reference repeats).

Set-up builds one ``repro.training.Trainer`` with ScalAna on, fed by the
benchmark's token rows, makes the train state on the device from the seed,
and drives the first ``setup_steps`` steps through ``Trainer.train`` — the
first compiled step, the first (eager, per-equation) sampled step, and a
second compiled one — keeping on the host what the reference compares: the
initial parameters, the optimizer's first moment after step 1 and the
parameters after the last set-up step.  The window then continues the same
trainer and state in whole sampling periods (K steps, one sampled).

``train_tokens_per_s`` counts the tokens of the whole periods that end
within the window over the time of those periods.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from typing import Dict, List, Tuple

import numpy as np

from harness import seed_words


class TokenFeed:
    """Token rows from the seed, one batch per step, every row distinct
    (uniform over the vocabulary).  Stands in for the trainer's dataset:
    ``Trainer`` asks ``batch(i)``; the driver advances ``step``."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.shape, self.vocab = seed, (batch, seq + 1), vocab
        self.step = 0

    def tokens(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(seed_words(self.seed, 1, step))
        return rng.integers(0, self.vocab, self.shape, dtype=np.int32)

    def batch(self, _index: int) -> Dict[str, np.ndarray]:
        return {"tokens": self.tokens(self.step)}


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norms in float64, keyed by the leaf's path."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        out[name] = float(np.linalg.norm(np.asarray(leaf, np.float64)))
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=None) -> Tuple[float, str]:
    """max over leaves of |prog - ref| / max(ref, median leaf's ref)."""
    names = [n for n in ref if keep is None or n in keep]
    floor = statistics.median(ref[n] for n in names)
    worst = max(names, key=lambda n: abs(prog[n] - ref[n])
                / max(ref[n], floor))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], floor), worst


def moved_leaves(grad_norms: Dict[str, float]) -> set:
    """Leaves whose reference gradient is above a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    floor = 1e-3 * statistics.median(grad_norms.values())
    return {n for n, g in grad_norms.items() if g > floor}


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.ref = cell.reference()
        self.arch = cell.config["arch"]
        self.seq = cell.config["shape"]["seq_len"]
        self.batch = cell.config["shape"]["batch"]
        self.K = cell.traffic["sample_every"]
        self.opt = cell.traffic["optimizer"]
        self.losses: List[float] = []
        self.attempted = self.failed = 0

    # -- the program ---------------------------------------------------
    def build(self):
        import jax
        from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
        from repro.training import Trainer

        arch = ArchConfig(**self.arch)
        run = RunConfig(arch=arch.name, scalana=True,
                        scalana_sample_every=self.K,
                        learning_rate=self.opt["learning_rate"],
                        warmup_steps=self.opt["warmup_steps"],
                        total_steps=self.opt["total_steps"],
                        weight_decay=self.opt["weight_decay"])
        tr = Trainer(run, arch_cfg=arch, shape=ShapeConfig(
            self.cell.name, self.seq, self.batch, "train"))
        self.feed = TokenFeed(self.cell.seed, self.batch, self.seq,
                              arch.vocab_size)
        tr.dataset = self.feed
        key = int(np.random.SeedSequence(
            seed_words(self.cell.seed, 0)).generate_state(1)[0] >> 1)
        state = self.ref.make_state(jax.eval_shape(tr.init_state), key)
        return tr, state

    def step(self):
        """One step through ``Trainer.train`` in the span ``train_step``;
        its host time is kept as a ``sampled_step`` or a
        ``compiled_step`` by whether the profiler's count of sampled
        steps moved."""
        def sampled_steps():        # the trainer builds its profiler lazily
            prof = self.tr.profiler
            return prof.sampled_steps if prof is not None else 0

        before = sampled_steps()
        with self.cell.span("train_step"):
            self.state = self.tr.train(num_steps=1, state=self.state)
        path = ("sampled_step" if sampled_steps() > before
                else "compiled_step")
        self.cell.spans[path].append(self.cell.spans["train_step"][-1])
        self.feed.step += 1
        loss = self.tr.metrics_log[-1]["loss"]
        self.losses.append(loss)
        return loss

    # -- harness hooks -------------------------------------------------
    def setup(self):
        import jax
        self.tr, self.state = self.build()
        self.params0 = jax.device_get(self.state.params)
        for i in range(self.cell.traffic["setup_steps"]):
            self.step()
            if i == 0:
                self.mu1 = jax.device_get(self.state.opt.mu)
        self.params_set = jax.device_get(self.state.params)
        self.setup_losses = list(self.losses)

    def window(self):
        K, start = self.K, time.perf_counter()
        self.periods: List[float] = []
        n0 = len(self.losses)
        while True:
            p0 = time.perf_counter()
            for _ in range(K):
                self.step()
            p1 = time.perf_counter()
            if p1 - start > self.cell.seconds:
                break                     # ended after the window closed
            self.periods.append(p1 - p0)
            if (p1 - start) + (p1 - p0) > self.cell.seconds:
                break                     # the next period would not fit
        window_losses = self.losses[n0:]
        self.attempted = len(window_losses)
        self.failed = sum(not math.isfinite(x) for x in window_losses)
        prof = self.tr.profiler
        mapped = {prof.mapping.get(v, prof.psg.root)
                  for v in prof.psg_full.children(prof.psg_full.root)}
        sampled = {v for v, vec in prof.perf_vectors().items()
                   if vec.samples > 0}
        self.unsampled = len(mapped - sampled)

    def end_to_end(self) -> Dict[str, float]:
        if not self.periods:
            raise RuntimeError(f"no sampling period of {self.K} steps ended "
                               f"within the {self.cell.seconds} s window")
        tokens = len(self.periods) * self.K * self.batch * self.seq
        rate = tokens / sum(self.periods)
        self.cell.raw["model_flops_per_token"] = \
            self.ref.model_flops_per_token(self.arch)
        return {"train_tokens_per_s": rate}

    def release(self):
        del self.state, self.tr
        gc.collect()

    def verify(self) -> List[Tuple[str, float, float]]:
        readings = self.readings(self.reference_run())
        lim = self.cell.limits
        return [(name, readings[name], lim[name]) for name in
                ("loss_gap", "grad_gap", "update_gap")] + [
            ("unsampled_vertices", float(self.unsampled),
             lim["unsampled_vertices"])]

    # -- the comparison ------------------------------------------------
    def batches(self) -> List[np.ndarray]:
        return [self.feed.tokens(s)
                for s in range(self.cell.traffic["setup_steps"])]

    def reference_run(self, matmul_dtype=None, rows: int = 0):
        """The reference over the set-up steps: (losses, first clipped
        gradient, parameters after the last set-up step)."""
        return self.ref.train_steps(self.arch, self.opt, self.params0,
                                    self.batches(),
                                    matmul_dtype=matmul_dtype, rows=rows)

    def readings(self, ref, prog=None) -> Dict[str, float]:
        """The numbers compared: the worst relative gap of the set-up
        steps' losses, the worst leaf's gap of the first gradient's norm
        and of the parameters' change over the set-up steps.  ``prog``
        defaults to the program's own run; pass another reference run to
        read a control or a fault against ``ref``."""
        b1 = self.opt["b1"]
        if prog is None:
            losses = self.setup_losses
            grad = {k: v / (1.0 - b1) for k, v in leaf_norms(self.mu1).items()}
            moved = self.params_set
        else:
            losses, g, moved = prog
            grad = leaf_norms(g)
        ref_losses, ref_g, ref_p = ref
        ref_grad = leaf_norms(ref_g)
        delta = lambda p: _tree_sub(p, self.params0)    # noqa: E731
        keep = moved_leaves(ref_grad)
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                          ref_losses))
        grad_gap, grad_leaf = worst_leaf_gap(grad, ref_grad)
        update_gap, update_leaf = worst_leaf_gap(
            leaf_norms(delta(moved)), leaf_norms(delta(ref_p)), keep)
        self.cell.log(f"losses {losses} reference {ref_losses}; worst "
                      f"gradient leaf {grad_leaf}, worst update leaf "
                      f"{update_leaf}; {len(ref_grad) - len(keep)} leaves "
                      f"left out of the update (reference gradient under "
                      f"1e-3 of the median leaf's)")
        return {"loss_gap": loss_gap, "grad_gap": grad_gap,
                "update_gap": update_gap}


def _tree_sub(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)
