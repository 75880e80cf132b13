"""Batched serving engine: request queue -> prefill -> decode loop.

A deliberately small but real continuous-batching engine: requests arrive
with prompts, get packed into a fixed batch, prefilled once, then decoded
step-by-step with greedy/temperature sampling until max tokens.  The same
`prefill`/`decode_step` functions are what the dry-run lowers at production
shapes.

An engine can be constructed with a `repro.CompiledNetwork`
(`compiled=...`, the facade artifact — preferred) or a bare `CoexecPlan`
(`coexec_plan=...`, the pre-facade spelling, still supported): a
deployment ships the offline partitioning artifact alongside the model
instead of re-planning at serving time — and the engine *executes* it.
`execute_plan()` lowers the plan's op graph — projection/linear and conv
nodes channel-split, attention/SSM decoder-block nodes through their
registered kernels, residual adds materialized — through `PlanExecutor`
onto the co-execution mesh, keeping the per-node fidelity report on
`engine.last_execution_report` for ops teams to compare executed against
planned latency.  With `compiled=` the engine shares the compiled
network's memoized executor; plans compiled from `graph.from_model`
configs execute the same way the legacy unit-chain plans do.

With `measurement_store=` (a `repro.measure.MeasurementStore` or a
directory path), every `execute_plan` call auto-appends its per-op
`MeasurementRecord`s to the store — the serving fleet becomes the
calibration data source — and `engine.drift` exposes how far the
executed-vs-predicted log-ratio has moved (trailing-window median vs
baseline-window median; the replanning trigger an ops team would alert
on, consumed automatically by `repro.serving.ContinuousScheduler`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Any, List, Optional, Tuple

import numpy as np

from repro.measure import trace

if TYPE_CHECKING:
    import jax
    from repro.models.config import ModelConfig
    from repro.runtime.executor import ExecutionReport, PlanExecutor
    from repro.runtime.plan import CoexecPlan


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0           # 0 = greedy
    frames: Optional[np.ndarray] = None  # enc-dec only
    arrival_s: float = 0.0             # admission time (scheduler traffic)


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: List[int]


def _greedy_tokens(logits):
    import jax.numpy as jnp
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _sampled_tokens(rng, logits, temps):
    import jax
    import jax.numpy as jnp
    greedy = _greedy_tokens(logits)
    rng, sub = jax.random.split(rng)
    safe = jnp.where(temps > 0.0, temps, 1.0)
    sampled = jax.random.categorical(
        sub, logits / safe[:, None], axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy), rng


@functools.cache
def _programs():
    """The two sampling programs, (greedy, sampled), jitted once per
    process (jax is imported on first use: this module is import-light)."""
    import jax
    return jax.jit(_greedy_tokens), jax.jit(_sampled_tokens)


def sample_tokens(rng, logits: jax.Array, temperatures
                  ) -> Tuple[np.ndarray, Any]:
    """Per-request sampling shared by the fixed-batch engine and the
    continuous scheduler: row i of `logits` samples at `temperatures[i]`
    (<= 0 = greedy; a scalar applies to every row).

    Greedy or sampled is decided on the host from the temperatures, so
    the choice waits for nothing on the device; each path is one jitted
    program.  The key is split (and thus consumed) only when some row
    samples, so all-greedy batches are rng-invariant.  The tokens are
    read back to the host once, inside a `repro.sched.read` span: that
    read is where the host waits for the decode step that made `logits`.
    Returns (tokens, rng): an int32 `np.ndarray` of shape (B,) and the
    key, which stays on the device."""
    import jax
    temps = np.asarray(temperatures, np.float32)
    if temps.ndim == 0:
        temps = np.full((logits.shape[0],), temps, np.float32)
    greedy, sampled = _programs()
    if (temps > 0.0).any():
        tokens, rng = sampled(rng, logits, temps)
    else:
        tokens = greedy(logits)
    with trace.span("repro.sched.read"):
        return jax.device_get(tokens), rng


class ServingEngine:
    def __init__(self, cfg: ModelConfig, model, params, *,
                 max_batch: int = 4, max_len: int = 128, seed: int = 0,
                 coexec_plan: Optional["CoexecPlan"] = None,
                 compiled=None, measurement_store=None):
        import jax
        self.cfg = cfg
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.rng = jax.random.PRNGKey(seed)
        if compiled is not None and coexec_plan is not None:
            raise ValueError("pass either compiled= (a repro.CompiledNetwork)"
                             " or coexec_plan= (a bare CoexecPlan), not both")
        if compiled is not None:
            if not (hasattr(compiled, "plan") and hasattr(compiled, "target")
                    and hasattr(compiled, "executor")):
                raise TypeError("compiled must be a repro.CompiledNetwork "
                                f"(got {type(compiled).__name__})")
            coexec_plan = compiled.plan
        elif coexec_plan is not None and \
                not hasattr(coexec_plan, "provenance"):
            raise TypeError("coexec_plan must be a repro.runtime CoexecPlan "
                            f"(got {type(coexec_plan).__name__})")
        self.compiled = compiled
        self.coexec_plan = coexec_plan
        if measurement_store is not None and \
                not hasattr(measurement_store, "append"):
            from repro.measure import MeasurementStore
            measurement_store = MeasurementStore(measurement_store)
        self.measurement_store = measurement_store
        self._fidelity_log: List[float] = []   # mean log(wall/pred) per run
        self._plan_executor: Optional["PlanExecutor"] = None
        self.last_execution_report: Optional["ExecutionReport"] = None
        self.last_batch_decode_steps = 0       # decode calls of last batch
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    @property
    def plan_executor(self) -> "PlanExecutor":
        """The runtime lowering of the shipped plan (built on first use;
        shared with the CompiledNetwork's memoized executor when one was
        passed)."""
        if self.coexec_plan is None:
            raise ValueError("engine was constructed without a compiled "
                             "network or coexec_plan")
        if self._plan_executor is None:
            if self.compiled is not None:
                self._plan_executor = self.compiled.executor()
            else:
                from repro.runtime.executor import PlanExecutor
                self._plan_executor = PlanExecutor(self.coexec_plan)
        return self._plan_executor

    def execute_plan(self, x: Optional[jax.Array] = None, *,
                     chain: bool = True,
                     warmup: bool = True) -> Tuple[jax.Array, Any]:
        """Execute the shipped plan on the co-execution mesh.

        Runs every scheduled unit — co-executed projection (linear) and
        conv layers channel-split across the device groups, exclusive ones
        unsplit — and records the executed-vs-predicted fidelity report on
        `self.last_execution_report` (and, when the engine has a
        `measurement_store`, appends the per-op records to it).  Returns
        (output, report).

        `warmup=True` (default) costs one untimed pass before the
        executor's *first* run only (the executor tracks what it already
        executed), so the recorded wall times — the calibration data
        source and the `drift` anchor — measure steady-state execution,
        never tracing + XLA compilation.
        """
        y, report = self.plan_executor.run(x, chain=chain, warmup=warmup)
        self.last_execution_report = report
        ratio = report.mean_log_ratio()
        if ratio is not None:
            self._fidelity_log.append(ratio)
        if self.measurement_store is not None:
            self.measurement_store.append(report)
        return y, report

    @property
    def drift(self) -> Optional[float]:
        """Windowed fidelity drift of the shipped plan: trailing-window
        median of the mean log(wall/pred) fidelity log minus its
        baseline-window median (0.0 = stable, positive = the plan got
        slower than planned — the replanning trigger).  Medians on both
        ends mean a single noisy run — first or latest — cannot poison
        the signal.  None until two executions have been observed."""
        from repro.measure.drift import windowed_drift
        return windowed_drift(self._fidelity_log)

    @property
    def drift_latest_vs_first(self) -> Optional[float]:
        """The pre-windowing drift spelling (latest run minus first run),
        kept for callers that want the raw two-point comparison."""
        if len(self._fidelity_log) < 2:
            return None
        return self._fidelity_log[-1] - self._fidelity_log[0]

    def _sample(self, logits: jax.Array, temperatures) -> np.ndarray:
        """Per-request sampling: row i of `logits` samples at
        `temperatures[i]` (<= 0 = greedy), so mixed greedy/temperature
        batches are correct.  All-greedy batches never consume rng.
        The tokens come back on the host."""
        tok, self.rng = sample_tokens(self.rng, logits, temperatures)
        return tok

    def run(self, requests: List[Request]) -> List[Completion]:
        out: List[Completion] = []
        for i in range(0, len(requests), self.max_batch):
            out.extend(self._run_batch(requests[i:i + self.max_batch]))
        return out

    def _run_batch(self, batch: List[Request]) -> List[Completion]:
        import jax.numpy as jnp
        b = len(batch)
        t = max(len(r.prompt) for r in batch)
        toks = np.zeros((b, t), np.int32)
        for i, r in enumerate(batch):
            toks[i, t - len(r.prompt):] = r.prompt     # left-pad
        toks = jnp.asarray(toks)
        # pad-aware attention stacks mask everything before each row's
        # first real token, so a short prompt padded behind a long one
        # decodes exactly as it would alone (RoPE phases are relative —
        # the constant shift cancels); recurrent/MLA stacks keep the
        # legacy shared-timeline semantics
        start = None
        if getattr(self.model, "pad_aware", False):
            start = jnp.asarray(
                np.array([t - len(r.prompt) for r in batch], np.int32))

        cache = self.model.init_cache(b, self.max_len)
        if self.cfg.is_encoder_decoder:
            frames = jnp.asarray(np.stack([
                r.frames if r.frames is not None else
                np.zeros((self.cfg.encoder_seq, self.cfg.d_model),
                         np.float32)
                for r in batch]))
            logits, cache = self._prefill(self.params, toks, cache, frames)
        elif start is not None:
            logits, cache = self._prefill(self.params, toks, cache,
                                          start=start)
        else:
            logits, cache = self._prefill(self.params, toks, cache)

        max_new = max(r.max_new_tokens for r in batch)
        # per-request temperatures: a greedy request stays greedy even when
        # batched behind a temperature-sampling one (batch[0] used to win)
        temps = np.array([r.temperature for r in batch], np.float32)
        generated = [[] for _ in range(b)]
        tok = self._sample(logits, temps)
        for i in range(b):
            generated[i].append(int(tok[i]))
        self.last_batch_decode_steps = 0
        for step in range(1, max_new):
            if all(len(g) >= r.max_new_tokens
                   for g, r in zip(generated, batch)):
                break                   # every request already done
            pos = jnp.int32(t + step - 1)
            if start is not None:
                logits, cache = self._decode(self.params, tok[:, None],
                                             cache, pos, start=start)
            else:
                logits, cache = self._decode(self.params, tok[:, None],
                                             cache, pos)
            self.last_batch_decode_steps += 1
            tok = self._sample(logits, temps)
            for i in range(b):
                if len(generated[i]) < batch[i].max_new_tokens:
                    generated[i].append(int(tok[i]))
        return [Completion(r.rid, g) for r, g in zip(batch, generated)]
