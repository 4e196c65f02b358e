"""Streaming trainer for deep BCPNN — the host-side driver of the
accelerator.

The paper's semi-unsupervised protocol (§5), generalized to any depth
(DESIGN.md §1): for each stack projection in turn, N epochs of
unsupervised representation learning (layerwise greedy — lower layers are
frozen feature extractors while a layer trains), then ONE supervised pass
on the readout projection, then inference.  Epochs run as a single jit'd
``lax.scan`` over batch-major data, so a whole epoch is one device
program — the TPU analogue of keeping the FPGA pipeline hot.

Fault-tolerant data-parallel fit (DESIGN.md §12): ``Trainer(cfg,
mesh=...)`` runs each epoch as the shard_map scan-over-batches program
(``distributed.data_parallel``) — bit-for-bit equal to the single-device
epoch — and ``fit(ckpt_dir=..., ckpt_every_batches=k)`` checkpoints
mid-fit with a schedule cursor in the manifest, so a fit interrupted by
worker loss resumes exactly where it stopped on whatever mesh
``elastic_mesh`` can still build.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..checkpoint import CheckpointManager
from .bcpnn_layer import ProjSpec, forward, is_patchy, learn_path
from .network import (
    DeepState,
    NetworkSpec,
    as_spec,
    infer,
    init_deep,
    spec_to_dict,
    supervised_readout_step,
    train_projection_step,
    unsupervised_layer_step,
)


def _batchify_padded(x: np.ndarray, batch: int):
    """Zero-pad to a whole number of batches; also return the (nb, B)
    validity mask marking genuine rows.  Unlike ``_batchify`` this loses
    no tail samples — evaluation masks the pad slots out of the mean."""
    n = x.shape[0]
    nb = max(1, -(-n // batch))
    pad = nb * batch - n
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
    valid = (np.arange(nb * batch) < n).astype(np.float32)
    return (x.reshape(nb, batch, *x.shape[1:]),
            valid.reshape(nb, batch))


@dataclasses.dataclass(frozen=True)
class FitCursor:
    """Where a fit stopped in the layerwise-greedy schedule — stored in
    the checkpoint manifest ``extra`` next to the spec, so a resumed fit
    (possibly on a rebuilt mesh) continues EXACTLY where the interrupted
    one left off.  ``batch`` counts batches of the current epoch already
    consumed; the cursor always names the NEXT work item."""

    phase: str = "unsupervised"   # "unsupervised" | "supervised" | "done"
    layer: int = 0
    epoch: int = 0
    batch: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FitCursor":
        return cls(phase=str(d["phase"]), layer=int(d["layer"]),
                   epoch=int(d["epoch"]), batch=int(d["batch"]))


def _rewires(spec: ProjSpec) -> bool:
    """True when ``spec``'s learn steps rewire (``maybe_rewire`` changes
    a mask only on a projection with a binding nact budget)."""
    return spec.struct_every > 0 and is_patchy(spec)


class _RewireClock:
    """The rewire points of one greedy phase, counted on the host: the
    projection's trace clock is read from the device once, the first time
    a recording span asks, and then advanced by the learn steps of each
    epoch call (one per batch)."""

    def __init__(self, trainer: "Trainer", layer: int):
        self.trainer, self.layer = trainer, layer
        spec = trainer.spec.projs[layer]
        self.every = spec.struct_every if _rewires(spec) else 0
        self.t: Optional[int] = None

    def take(self, n: int, on: bool) -> Optional[int]:
        """Rewire points among the next ``n`` learn steps; None when they
        are not being counted (no recording span has asked yet)."""
        if self.every <= 0:
            return 0
        if self.t is None:
            if not on:
                return None
            self.t = int(self.trainer.state.projs[self.layer].traces.t)
        t0, self.t = self.t, self.t + n
        return (t0 + n) // self.every - t0 // self.every


@functools.partial(jax.jit, static_argnames=("spec", "layer"),
                   donate_argnums=(0,))
def unsupervised_layer_epoch(state: DeepState, spec: NetworkSpec,
                             xs: jax.Array, layer: int) -> DeepState:
    """xs: (nbatch, B, Ni) — one unsupervised epoch on stack projection
    ``layer``, fully on device."""
    def body(st, x):
        return unsupervised_layer_step(st, spec, x, layer), None
    state, _ = jax.lax.scan(body, state, xs)
    return state


def unsupervised_epoch(state: DeepState, spec_or_cfg, xs: jax.Array,
                       layer: int = 0) -> DeepState:
    """Legacy entry point (depth-1 networks train their only projection)."""
    return unsupervised_layer_epoch(state, as_spec(spec_or_cfg), xs, layer)


@functools.partial(jax.jit, static_argnames=("spec", "layer"),
                   donate_argnums=(0,))
def _train_projection_epoch(state: DeepState, spec: NetworkSpec,
                            hs: jax.Array, layer: int) -> DeepState:
    """One epoch over PRECOMPUTED layer-input rates hs: (nbatch, B, N_l)."""
    def body(st, h):
        return train_projection_step(st, spec, h, layer), None
    state, _ = jax.lax.scan(body, state, hs)
    return state


@functools.partial(jax.jit, static_argnames=("spec", "layer"))
def _propagate_batches(state: DeepState, spec: NetworkSpec, xs: jax.Array,
                       layer: int) -> jax.Array:
    """Push batched rates through the (now frozen) projection ``layer``."""
    return jax.lax.map(
        lambda xb: forward(state.projs[layer], spec.projs[layer], xb), xs)


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _supervised_epoch(state: DeepState, spec: NetworkSpec, xs: jax.Array,
                      ys: jax.Array) -> DeepState:
    def body(st, xy):
        x, y = xy
        return supervised_readout_step(st, spec, x, y), None
    state, _ = jax.lax.scan(body, state, (xs, ys))
    return state


def supervised_epoch(state: DeepState, spec_or_cfg, xs: jax.Array,
                     ys: jax.Array) -> DeepState:
    return _supervised_epoch(state, as_spec(spec_or_cfg), xs, ys)


@functools.partial(jax.jit, static_argnames=("spec", "layer"),
                   donate_argnums=(0,))
def _train_projection_epoch_masked(state: DeepState, spec: NetworkSpec,
                                   hs: jax.Array, valid: jax.Array,
                                   layer: int) -> DeepState:
    """The masked twin of ``_train_projection_epoch``: ``valid`` (nb, B)
    marks genuine rows, so the zero-padded tail batch divides its stats
    by the REAL row count instead of diluting the traces (or, before the
    pad existed at all, being silently dropped).  Fits whose data does
    not divide the batch run every step of every epoch here; on the
    pallas backend those steps take the fused update kernel with the row
    count as a runtime operand (``learn_masked``).  Whole-batch fits keep
    the unmasked epoch bit-for-bit."""
    def body(st, hv):
        h, v = hv
        return train_projection_step(st, spec, h, layer, valid=v), None
    state, _ = jax.lax.scan(body, state, (hs, valid))
    return state


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _supervised_epoch_masked(state: DeepState, spec: NetworkSpec,
                             xs: jax.Array, ys: jax.Array,
                             valid: jax.Array) -> DeepState:
    def body(st, xyv):
        x, y, v = xyv
        return supervised_readout_step(st, spec, x, y, valid=v), None
    state, _ = jax.lax.scan(body, state, (xs, ys, valid))
    return state


@functools.partial(jax.jit, static_argnames=("spec",))
def _eval_batches(state: DeepState, spec: NetworkSpec, xs: jax.Array,
                  ys: jax.Array, valid: jax.Array) -> jax.Array:
    """Accuracy over genuine samples only: correct/total are accumulated
    under the validity mask, so a zero-padded tail batch neither skews the
    mean (the old per-batch average weighted short batches equally) nor
    contributes phantom predictions."""
    def body(carry, xyv):
        x, y, v = xyv
        _, pred = infer(state, spec, x, valid=v)
        correct, total = carry
        correct = correct + jnp.sum((pred == y).astype(jnp.float32) * v)
        return (correct, total + jnp.sum(v)), None
    (correct, total), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())), (xs, ys, valid))
    return correct / jnp.maximum(total, 1.0)


def eval_batches(state: DeepState, spec_or_cfg, xs: jax.Array,
                 ys: jax.Array, valid: Optional[jax.Array] = None) -> jax.Array:
    """Mean accuracy over (nbatch, B, ...) eval data; ``valid`` (optional,
    (nbatch, B) 0/1) masks padded rows out of the mean."""
    if valid is None:
        valid = jnp.ones(ys.shape[:2], jnp.float32)
    return _eval_batches(state, as_spec(spec_or_cfg), xs, ys, valid)


def evaluate_padded(state: DeepState, spec_or_cfg, x: np.ndarray,
                    y: np.ndarray, batch: int = 128) -> float:
    """Accuracy of ``state`` over the FULL unbatched eval set: the tail is
    zero-padded to a whole batch and masked out of the mean, not dropped.
    Shared by ``Trainer.evaluate`` and the serving drivers."""
    if len(x) != len(y):
        raise ValueError(f"x has {len(x)} samples but y has {len(y)} labels")
    xs, valid = _batchify_padded(np.asarray(x), batch)
    ys, _ = _batchify_padded(np.asarray(y, np.int32), batch)
    return float(eval_batches(state, spec_or_cfg, jnp.asarray(xs),
                              jnp.asarray(ys), jnp.asarray(valid)))


class Trainer:
    """End-to-end driver mirroring the paper's experimental protocol.

    Accepts either a legacy ``BCPNNConfig`` (the paper's depth-1 network)
    or a ``NetworkSpec`` of any depth; ``epochs`` in ``fit`` applies per
    stack projection (layerwise greedy schedule).

    ``mesh`` (optional ``jax.sharding.Mesh`` with a ``data_axis`` axis)
    turns every epoch into the shard_map data-parallel program — batches
    shard over rows, learning all-reduces disjoint-support trace partials
    (distributed/data_parallel.py), and the resulting state is
    bit-for-bit what the single-device fit produces.  Checkpointing and
    cursor resume (``fit``'s ``ckpt_*``/``resume`` arguments) work in
    both modes and across mesh changes, which is what makes worker-loss
    recovery exact: rebuild a smaller mesh with ``elastic_mesh``, resume
    from the cursor, and the final state matches the uninterrupted run.
    """

    def __init__(self, cfg, seed: int = 0, mesh=None,
                 data_axis: str = "data"):
        self.cfg = cfg
        self.spec = as_spec(cfg)
        self.state = init_deep(self.spec, jax.random.PRNGKey(seed))
        self.mesh = mesh
        self.data_axis = data_axis
        self.timer = None  # the last fit's StepTimer
        self._epoch_cache: Dict[tuple, Tuple[Callable, str]] = {}
        if mesh is not None:
            # Fail at construction, not mid-fit: every projection the DP
            # programs touch needs whole post-HCs per shard.
            from ..distributed.data_parallel import _check_geometry
            _check_geometry(self.spec, self.spec.depth - 1,
                            mesh.shape[data_axis])

    def reset(self, seed: int = 0) -> None:
        """Re-initialize the network state (fresh PRNG chain) while
        keeping the compiled epoch programs — what a warmup-then-measure
        benchmark run wants."""
        self.state = init_deep(self.spec, jax.random.PRNGKey(seed))

    # -------------------------------------------------- epoch programs --
    def _learn(self, pspec: ProjSpec, masked: bool) -> str:
        """The update path an epoch program's learn steps dispatch: the
        data-parallel steps always compute jnp stats."""
        return "jnp" if self.mesh is not None else learn_path(pspec, masked)

    def _unsup_fn(self, layer: int, masked: bool) -> Tuple[Callable, str]:
        """Epoch program for one greedy phase — single-device jit or the
        mesh's shard_map scan, cached per (layer, masked) with the update
        path its learn steps take."""
        key = ("unsup", layer, masked)
        if key not in self._epoch_cache:
            if self.mesh is None:
                if masked:
                    fn = lambda st, hs, v: _train_projection_epoch_masked(  # noqa: E731
                        st, self.spec, hs, v, layer)
                else:
                    fn = lambda st, hs: _train_projection_epoch(  # noqa: E731
                        st, self.spec, hs, layer)
            else:
                from ..distributed.data_parallel import (
                    make_data_parallel_projection_epoch)
                fn = make_data_parallel_projection_epoch(
                    self.spec, self.mesh, layer=layer, axis=self.data_axis,
                    masked=masked)
            self._epoch_cache[key] = (
                fn, self._learn(self.spec.projs[layer], masked))
        return self._epoch_cache[key]

    def _sup_fn(self, masked: bool) -> Tuple[Callable, str]:
        key = ("sup", masked)
        if key not in self._epoch_cache:
            if self.mesh is None:
                if masked:
                    fn = lambda st, xs, ys, v: _supervised_epoch_masked(  # noqa: E731
                        st, self.spec, xs, ys, v)
                else:
                    fn = lambda st, xs, ys: _supervised_epoch(  # noqa: E731
                        st, self.spec, xs, ys)
            else:
                from ..distributed.data_parallel import (
                    make_data_parallel_supervised_epoch)
                fn = make_data_parallel_supervised_epoch(
                    self.spec, self.mesh, axis=self.data_axis, masked=masked)
            self._epoch_cache[key] = (fn, self._learn(self.spec.readout,
                                                      masked))
        return self._epoch_cache[key]

    def fit(
        self,
        x_train: np.ndarray,
        y_train: np.ndarray,
        epochs: int,
        batch: int = 128,
        log: bool = False,
        ckpt_dir: Optional[str] = None,
        ckpt_every_batches: int = 0,
        resume: bool = False,
        on_chunk: Optional[Callable[[FitCursor], None]] = None,
    ) -> Dict[str, float]:
        """Layerwise unsupervised epochs + one supervised pass.

        The tail batch is zero-padded and masked, never dropped: a fit on
        n samples trains on all n (stats divide by genuine rows —
        ``learn_masked``), where it used to silently discard up to
        ``batch - 1`` of them.  Whole-batch data takes the exact same
        programs as before.

        Fault tolerance: with ``ckpt_dir`` + ``ckpt_every_batches > 0``
        the fit checkpoints every k batches (state + spec + schedule
        cursor, blocking) and ``resume=True`` continues from the latest
        such checkpoint.  ``on_chunk(cursor)`` fires after every chunk
        (post-checkpoint) — the fault-injection seam: raising
        ``WorkerLost`` from it aborts the fit with the checkpoint
        already on disk.  With ``ckpt_dir`` alone the fit writes one
        final resumable checkpoint.

        Returns ``train_ms_per_img``, the unsupervised epochs' time per
        image and pass (depth * epochs passes over the data), and
        ``straggler_events``, the epochs the fit's ``StepTimer`` flagged.

        Spans (``repro.spans``, on while a profiler session is active):
        ``trainer.fit`` around the call; inside it ``trainer.prepare``
        (batching and the copies to the device), one ``trainer.epoch``
        per epoch program call (``trainer.dispatch``, ``trainer.block``
        and, when it saves, ``trainer.checkpoint``; its arg ``learn``
        names the update path of the program's learn steps,
        ``learn_path``, and ``rewires`` the structural-plasticity rewire
        points among them, counted on the host from the trace clock) and
        ``trainer.propagate`` between layers.  For a network that rewires,
        ``trainer.fit`` carries ``rewired``: the (pre-HC, post-HC) mask
        entries that changed over the fit (one host copy of each mask
        before and after, made only while the span records).
        """
        with spans.span("trainer.fit", images=int(np.shape(x_train)[0]),
                        epochs=epochs, batch=batch) as sp:
            before = self._struct_masks() if sp.on else {}
            out = self._fit(x_train, y_train, epochs, batch, log, ckpt_dir,
                            ckpt_every_batches, resume, on_chunk)
            if before:
                after = self._struct_masks()
                sp.set(rewired=int(sum(np.sum(before[l] != after[l])
                                       for l in before)))
            return out

    def _struct_masks(self) -> Dict[int, np.ndarray]:
        """Host copies of the masks of the stack projections that rewire."""
        return {l: np.asarray(self.state.projs[l].mask)
                for l, p in enumerate(self.spec.projs) if _rewires(p)}

    def _fit(self, x_train, y_train, epochs, batch, log, ckpt_dir,
             ckpt_every_batches, resume, on_chunk) -> Dict[str, float]:
        from ..distributed.fault import StepTimer

        with spans.span("trainer.prepare"):
            xs_np, valid_np = _batchify_padded(np.asarray(x_train), batch)
            ys_np, _ = _batchify_padded(np.asarray(y_train, np.int32), batch)
            masked = bool(float(valid_np.min()) < 1.0)
            xs = jnp.asarray(xs_np)
            ys = jnp.asarray(ys_np)
            valid = jnp.asarray(valid_np)
        nb = int(xs.shape[0])
        if self.mesh is not None:
            n_shards = int(self.mesh.shape[self.data_axis])
            if batch % n_shards:
                raise ValueError(
                    f"batch={batch} rows cannot shard over the "
                    f"{n_shards}-way '{self.data_axis}' mesh axis")
        mgr = CheckpointManager(ckpt_dir) if ckpt_dir is not None else None
        if resume and mgr is None:
            raise ValueError("fit(resume=True) requires ckpt_dir")
        cursor = FitCursor()
        if resume and mgr.latest_step() is not None:
            step = mgr.latest_step()
            extra = mgr.read_extra(step) or {}
            if "cursor" not in extra:
                raise ValueError(
                    f"checkpoint step_{step} under {ckpt_dir} carries no "
                    f"fit cursor — it is a final artifact, not a mid-fit "
                    f"checkpoint (restore it with Trainer.restore)")
            self.state = mgr.restore(step, self.state)
            cursor = FitCursor.from_dict(extra["cursor"])
            if log:
                print(f"  resumed step_{step} at {cursor}")
        timer = StepTimer()
        self.timer = timer
        unsup_s = 0.0

        def save(cur: FitCursor) -> None:
            if mgr is not None and ckpt_every_batches > 0:
                with spans.span("trainer.checkpoint"):
                    mgr.save(int(self.state.step), self.state,
                             blocking=True,
                             extra={"spec": spec_to_dict(self.spec),
                                    "cursor": cur.to_dict()})

        def run_epoch(program: Tuple[Callable, str], operands: tuple,
                      start_b: int, tag: str,
                      cursor_at: Callable[[int], FitCursor],
                      clock: Optional[_RewireClock] = None):
            """One epoch from batch ``start_b``, in checkpoint-delimited
            chunks (the whole epoch at once when not checkpointing).
            Chunking cannot change the result: the scan carries the state
            through bit-unchanged, and each step's arithmetic is pinned
            by its optimization barriers."""
            nonlocal unsup_s
            fn, learn = program
            b0 = start_b
            while b0 < nb:
                n = (nb - b0 if ckpt_every_batches <= 0
                     else min(ckpt_every_batches, nb - b0))
                with spans.timed("trainer.epoch", tag=tag, batches=n,
                                 learn=learn) as ep:
                    ep.set(rewires=clock.take(n, ep.on) if clock else 0)
                    with spans.span("trainer.dispatch"):
                        sl = tuple(op[b0:b0 + n] for op in operands)
                        self.state = fn(self.state, *sl)
                    with spans.span("trainer.block"):
                        jax.block_until_ready(self.state)
                    step = int(self.state.step)
                    b0 += n
                    cur = cursor_at(b0)
                    save(cur)
                timer.record(ep.dt, step, tag=tag)
                if tag.startswith("unsup/"):
                    unsup_s += ep.dt
                if on_chunk is not None:
                    on_chunk(cur)

        if cursor.phase == "unsupervised":
            # Greedy phases reuse the frozen representation: ``cur`` holds
            # the dataset's rates at the current layer's input, computed
            # once per phase instead of once per step inside every epoch —
            # and recomputed (deterministic) up to the cursor on resume.
            cur = xs
            for l in range(cursor.layer):
                with spans.span("trainer.propagate"):
                    cur = _propagate_batches(self.state, self.spec, cur, l)
            for layer in range(cursor.layer, self.spec.depth):
                first = layer == cursor.layer
                program = self._unsup_fn(layer, masked)
                operands = (cur, valid) if masked else (cur,)
                clock = _RewireClock(self, layer)
                for e in range(cursor.epoch if first else 0, epochs):
                    start_b = cursor.batch if first and e == cursor.epoch \
                        else 0

                    def cursor_at(b, layer=layer, e=e):
                        if b < nb:
                            return FitCursor("unsupervised", layer, e, b)
                        if e + 1 < epochs:
                            return FitCursor("unsupervised", layer, e + 1, 0)
                        if layer + 1 < self.spec.depth:
                            return FitCursor("unsupervised", layer + 1, 0, 0)
                        return FitCursor("supervised", self.spec.depth, 0, 0)

                    run_epoch(program, operands, start_b,
                              f"unsup/L{layer}/e{e}", cursor_at, clock)
                    if log:
                        print(f"  layer {layer + 1}/{self.spec.depth} "
                              f"unsupervised epoch {e + 1}/{epochs} done")
                if layer + 1 < self.spec.depth:
                    with spans.span("trainer.propagate"):
                        cur = _propagate_batches(self.state, self.spec, cur,
                                                 layer)
            cursor = FitCursor("supervised", self.spec.depth, 0, 0)
        if cursor.phase == "supervised":
            program = self._sup_fn(masked)
            operands = (xs, ys, valid) if masked else (xs, ys)

            def sup_cursor_at(b):
                if b < nb:
                    return FitCursor("supervised", self.spec.depth, 0, b)
                return FitCursor("done", self.spec.depth, 0, 0)

            run_epoch(program, operands, cursor.batch, "sup/readout",
                      sup_cursor_at)
            cursor = FitCursor("done", self.spec.depth, 0, 0)
        if mgr is not None:
            with spans.span("trainer.checkpoint"):
                mgr.save(int(self.state.step), self.state, blocking=True,
                         extra={"spec": spec_to_dict(self.spec),
                                "cursor": cursor.to_dict()})
        n_img = int(valid_np.sum())
        return {
            "train_ms_per_img": 1e3 * unsup_s
            / max(1, n_img * epochs * self.spec.depth),
            "straggler_events": float(len(timer.events)),
        }

    def evaluate(self, x: np.ndarray, y: np.ndarray, batch: int = 128) -> float:
        """Accuracy over the FULL eval set: the last partial batch is
        zero-padded and masked out of the mean rather than dropped."""
        return evaluate_padded(self.state, self.spec, x, y, batch)

    def predict(self, x: np.ndarray) -> np.ndarray:
        _, pred = infer(self.state, self.spec, jnp.asarray(x))
        return np.asarray(pred)

    # ------------------------------------------------------ checkpoints --
    def save(self, directory: str, step: Optional[int] = None) -> None:
        """Blocking checkpoint of the full DeepState pytree.  The spec is
        stored alongside (manifest ``extra``), so serving can rebuild the
        network from the checkpoint directory alone."""
        mgr = CheckpointManager(directory)
        mgr.save(step if step is not None else int(self.state.step),
                 self.state, blocking=True,
                 extra={"spec": spec_to_dict(self.spec)})

    def restore(self, directory: str, step: Optional[int] = None) -> int:
        """Restore the latest (or a specific) checkpoint into this trainer.
        The target structure comes from the current spec, so depth or
        geometry mismatches fail with a clear error."""
        mgr = CheckpointManager(directory)
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
        self.state = mgr.restore(step, self.state)
        return step
