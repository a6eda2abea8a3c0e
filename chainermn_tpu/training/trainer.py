"""Minimal trainer/updater loop.

The reference delegates its training loop to Chainer's
``Trainer``/``StandardUpdater`` and integrates via extensions (SURVEY.md
§3.1). This standalone rebuild ships a lean equivalent: an updater that
feeds global batches (sharded over the communicator's mesh axis) into one
jitted train step, and a trainer with interval-triggered extensions — enough
to run every reference example shape (log/print/eval/snapshot at triggers,
rank-0-only reporting convention).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu import tracing


def default_converter(batch):
    """List of (x, y) pairs → stacked arrays (the reference's concat_examples)."""
    xs = np.stack([b[0] for b in batch])
    ys = np.stack([b[1] for b in batch])
    return xs, ys


class StandardUpdater:
    """Pulls a batch, shards it over the data axis, runs the jitted step.

    ``step_fn(state, *batch_arrays) -> (state, metrics_dict)`` must already
    be jitted (with the collective ops compiled in — see
    create_multi_node_optimizer). ``state`` is any pytree the caller owns.
    """

    def __init__(self, iterator, step_fn: Callable, state: Any, comm,
                 converter: Callable = default_converter):
        self.iterator = iterator
        self.step_fn = step_fn
        self.state = state
        self.comm = comm
        self.converter = converter
        self.iteration = 0
        self._dispatched = False     # the step has had its first call
        self.last_metrics: Dict[str, float] = {}
        axes = comm.axis_names
        self._data_sharding = NamedSharding(
            comm.mesh, P(axes if len(axes) > 1 else axes[0])
        )

    @property
    def epoch(self):
        return getattr(self.iterator, "epoch", 0)

    @property
    def is_new_epoch(self):
        return getattr(self.iterator, "is_new_epoch", False)

    def shard_batch(self, arrays):
        n = self.comm.size
        if jax.process_count() > 1:
            # each process feeds its LOCAL rows; assemble the global
            # sharded array without any host ever holding the full batch
            n_local = jax.local_device_count()
            for a in arrays:
                if hasattr(a, "shape") and a.shape and (
                        a.shape[0] % n_local != 0):
                    raise ValueError(
                        f"per-process batch size {a.shape[0]} is not "
                        f"divisible by this process's {n_local} local "
                        "devices — every process must feed a local row "
                        f"count that is a multiple of {n_local} (and all "
                        "processes must feed the same count, or "
                        "make_array_from_process_local_data will raise a "
                        "shape error)"
                    )
            return tuple(
                jax.make_array_from_process_local_data(
                    self._data_sharding, np.asarray(a))
                for a in arrays
            )
        for a in arrays:
            if hasattr(a, "shape") and a.shape and a.shape[0] % n != 0:
                raise ValueError(
                    f"global batch size {a.shape[0]} is not divisible by the "
                    f"{n} devices of the data axis — pick a batch size that "
                    f"is a multiple of {n}"
                )
        return tuple(
            jax.device_put(a, self._data_sharding) for a in arrays
        )

    def update(self):
        with tracing.span("updater.update", iteration=self.iteration):
            with tracing.span("updater.input") as sp:
                batch = next(self.iterator)
                arrays = self.converter(batch)
                if sp:
                    sp.set(bytes=sum(getattr(a, "nbytes", 0)
                                     for a in arrays))
                arrays = self.shard_batch(arrays)
            with tracing.span("updater.dispatch"):
                if self._dispatched:
                    self.state, metrics = self.step_fn(self.state, *arrays)
                else:
                    self.state, metrics = self._first_call(arrays)
            self.last_metrics = metrics
            self.iteration += 1

    def _first_call(self, arrays):
        """This updater's first dispatch of the step, blocked on: the step's
        compile rows (tracing.py) and its executable's first run lie inside
        the ``program.first_call`` span."""
        self._dispatched = True
        with tracing.lifecycle_span("program.first_call",
                                    program="local_step"):
            return jax.block_until_ready(self.step_fn(self.state, *arrays))

    # -- full-state resume (docs/fault_tolerance.md) --------------------

    def host_state_dict(self) -> Dict[str, Any]:
        """Host-side training position for checkpoints: iteration count,
        iterator position/epoch/RNG, and the global NumPy RNG (augment
        pipelines draw from it). Everything here is small and picklable;
        the device pytree (``self.state``) is snapshotted separately."""
        it_state = getattr(self.iterator, "state_dict", None)
        return {
            "iteration": self.iteration,
            "iterator": it_state() if callable(it_state) else None,
            "np_random": np.random.get_state(),
        }

    def load_host_state(self, host: Dict[str, Any]) -> None:
        """Restore :meth:`host_state_dict` output — the resumed run draws
        the exact next batch the interrupted run would have."""
        self.iteration = int(host.get("iteration", self.iteration))
        it_state = host.get("iterator")
        restore = getattr(self.iterator, "load_state_dict", None)
        if it_state is not None and callable(restore):
            restore(it_state)
        if host.get("np_random") is not None:
            np.random.set_state(host["np_random"])


class _Entry:
    def __init__(self, ext, trigger, name):
        self.ext = ext
        self.n, self.unit = trigger
        self.name = name
        self._last_epoch = 0
        self.closed = False

    def due(self, updater) -> bool:
        if self.unit == "iteration":
            return updater.iteration % self.n == 0
        if self.unit == "epoch":
            if updater.is_new_epoch and updater.epoch % self.n == 0:
                return True
            return False
        raise ValueError(f"unknown trigger unit {self.unit!r}")


class Trainer:
    """Runs the updater until the stop trigger, firing extensions.

    Reference convention preserved: attach reporting extensions only on the
    master (``if comm.rank == 0: trainer.extend(...)``) — metric reduction
    happens in-graph or via the multi-node evaluator, not here.

    Resilience (docs/fault_tolerance.md): with ``handle_preemption=True``
    (default) the run installs a SIGTERM/SIGINT flag handler and polls it
    every step — a preemption triggers an emergency checkpoint on every
    extension that offers ``emergency_save`` (the multi-node
    checkpointer), then a clean loop exit with ``trainer.preempted`` set.
    Any exception escaping the step loop also gets the last-chance
    checkpoint before extensions are finalized, so partial-epoch progress
    survives crashes. The chaos harness's step hook and the peer-death
    watchdog (``$CHAINERMN_TPU_WATCHDOG``) ride the same per-step poll.
    """

    def __init__(self, updater: StandardUpdater,
                 stop_trigger: Tuple[int, str] = (1, "epoch"),
                 out: str = "result", handle_preemption: bool = True):
        self.updater = updater
        self.stop_n, self.stop_unit = stop_trigger
        self.out = out
        self.handle_preemption = handle_preemption
        self.preempted = False
        self._extensions = []
        self.observation: Dict[str, float] = {}

    def extend(self, extension, trigger: Tuple[int, str] = (1, "epoch"),
               name: Optional[str] = None):
        self._extensions.append(_Entry(extension, trigger, name))

    def _stopped(self) -> bool:
        if self.stop_unit == "epoch":
            return self.updater.epoch >= self.stop_n
        return self.updater.iteration >= self.stop_n

    def _materialize_observation(self, start):
        # float() blocks on the device — do it only when someone will read
        # the numbers, so async dispatch keeps the device pipeline full.
        # update (not replace): extension-published keys (validation/...)
        # stay visible until their next refresh
        self.observation.update(
            {k: float(v) for k, v in self.updater.last_metrics.items()}
        )
        self.observation["iteration"] = self.updater.iteration
        self.observation["epoch"] = self.updater.epoch
        self.observation["elapsed_time"] = time.time() - start

    def _emergency_checkpoint(self, deadline_s=None) -> bool:
        """Fire ``emergency_save`` on every extension offering it (the
        multi-node checkpointer). Failures are printed, not raised — this
        runs on the way OUT of a dying/preempted run, where a save error
        must not mask the original exit path."""
        fired = False
        for e in self._extensions:
            fn = getattr(e.ext, "emergency_save", None)
            if callable(fn):
                try:
                    fn(self, deadline_s=deadline_s)
                    fired = True
                except Exception:
                    import traceback

                    traceback.print_exc()
        return fired

    def exit_code(self) -> int:
        """Process exit status under the supervisor contract
        (resilience/supervisor.py): :data:`PREEMPTED_EXIT_CODE` (143)
        after a preempted run — the supervisor restarts it for free —
        else 0. Train scripts: ``sys.exit(trainer.exit_code())``, or
        wrap the whole main in
        :func:`chainermn_tpu.resilience.supervisor.main_exit_code`
        (which also maps ``JobAbortedError`` to the aborted code)."""
        from chainermn_tpu.resilience.preemption import PREEMPTED_EXIT_CODE

        return PREEMPTED_EXIT_CODE if self.preempted else 0

    def run(self):
        if any(e.closed for e in self._extensions):
            # a prior run() finalized extensions holding external
            # resources; silently skipping (or re-firing) them would lose
            # data — resuming needs a fresh Trainer
            raise RuntimeError(
                "this Trainer already ran and finalized its extensions; "
                "construct a new Trainer (re-attaching extensions) to "
                "resume")
        from chainermn_tpu.resilience import chaos, preemption, watchdog

        guard = None
        if self.handle_preemption:
            guard = preemption.install_preemption_handler()
        wd = watchdog.maybe_start_watchdog()
        start = time.time()
        try:
            try:
                while not self._stopped():
                    # chaos first: an injected SIGTERM at this step is
                    # visible to the preemption poll on the next line
                    chaos.on_step(self.updater.iteration)
                    if wd is not None:
                        wd.check()
                    if guard is not None and guard.requested:
                        self.preempted = True
                        self._emergency_checkpoint(guard.grace_deadline())
                        break
                    try:
                        self.updater.update()
                    except StopIteration:
                        break  # non-repeating iterator exhausted
                    due = [e for e in self._extensions
                           if e.due(self.updater)]
                    if due:
                        self._materialize_observation(start)
                        for e in due:
                            with tracing.span(
                                    "trainer.extension",
                                    name=e.name or type(e.ext).__name__):
                                e.ext(self)
                self._materialize_observation(start)
            except BaseException:
                # last-chance checkpoint: partial-epoch progress survives
                # any exception leaving the step loop (the consensus
                # election picks it up on restart); then re-raise
                self._emergency_checkpoint()
                raise
        finally:
            if guard is not None:
                guard.uninstall()
            # finalize extensions that hold external resources (an open
            # jax.profiler trace, checkpoint writers) even when the run ends
            # before their stop condition or raises
            for e in self._extensions:
                if e.closed:
                    continue  # a prior run() already released it
                close = getattr(e.ext, "close", None)
                if callable(close):
                    e.closed = True
                    try:
                        close()
                    except Exception:
                        import traceback

                        traceback.print_exc()
