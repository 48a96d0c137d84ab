"""One run of one cell: set-up, the measured window, the check.

A cell (``workloads/<name>.json``) names a configuration
(``configs/<config>.json``: the model, its family in
``models/<family>.py``, the graph and its generator in
``graphs/<generator>.py``) and a traffic mix (``traffic/<traffic>.json``:
partitions, scheme, policy, wire, evaluation period, job length, chips).
The window drives the program's normal entry, ``repro.train.train_gnn``,
for a job of ``horizon_epochs`` epochs, with the model the family
describes (``program_fields``):

* set-up is everything from the start of the process to the end of
  epoch 0's evaluation: the graph, ``train_gnn``'s partition and halo
  set-up, tracing and compiling the step and the evaluation, epoch 0;
* the window starts there and closes at the first evaluation that ends
  ``--seconds`` or more after it started; ``epoch_s`` is its wall time
  over the epochs it completed, evaluations included;
* ``correct`` compares the program's first steps, as the window's own
  call produced them, with the family's reference (``reference.py``, see
  ``check.py``) once the window has closed and the program's state is
  freed.

With ``trace`` the window runs under the profiler and the run reports
the per-layer metrics of ``metrics/<name>.py`` instead of the end-to-end
ones.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
OUT = CHECKOUT / "bench_out"
#: the directories searched, in order, for a cell's files by kind
#: (``workloads``, ``configs``, ``traffic``, ``models``, ``graphs``,
#: ``metrics``)
ROOTS = [BENCH]
#: the fields of the program's model that ``train_gnn`` takes by name;
#: the others reach its ``GNNConfig`` bound to the class it builds
TRAIN_GNN_FIELDS = ("conv", "hidden", "layers")

#: the steps whose losses the check compares; their weights are read as
#: the next step gets them
CHECKED_STEPS = 3


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from ``train_gnn``'s log hook to end the job early."""


def find(kind: str, file: str) -> Path:
    for root in ROOTS:
        if (root / kind / file).exists():
            return root / kind / file
    raise FileNotFoundError(f"no {kind}/{file} under "
                            f"{[str(r) for r in ROOTS]}")


def load(kind: str, name: str) -> dict:
    return json.loads(find(kind, f"{name}.json").read_text())


def load_module(kind: str, name: str):
    path = find(kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Backend compilations JAX reports (a hit in the persistent cache is
    not one)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class StepRecorder:
    """Wraps the program's step builders so that the first steps' inputs
    and outputs are kept: references to device arrays, no copy and no
    synchronisation, so the window's timing is untouched."""

    def __init__(self, keep: int):
        self.keep = keep
        self.calls: list = []

    def wrap(self, builder):
        def build(*args, **kwargs):
            step = builder(*args, **kwargs)

            def recorded(params, opt_state, *rest):
                out = step(params, opt_state, *rest)
                if len(self.calls) < self.keep:
                    self.calls.append((params, out[1], out[2]["loss"]))
                return out

            return recorded
        return build

    def readings(self, b1: float) -> dict:
        """The program's readings in ``reference.train_readings``' form."""
        from reference import flat
        if len(self.calls) < self.keep:
            raise RuntimeError(f"the window ran {len(self.calls)} steps; "
                               f"the check needs {self.keep}")
        mu = flat(self.calls[0][1]["mu"])
        return {"loss": np.asarray([float(c[2]) for c in
                                    self.calls[:self.keep - 1]]),
                "grad1": {k: v / (1.0 - b1) for k, v in mu.items()},
                "p0": flat(self.calls[0][0]),
                "pn": flat(self.calls[self.keep - 1][0])}


class Window:
    """``train_gnn``'s ``log_fn``: it is called after each evaluation,
    once the step's loss and the accuracies are on the host."""

    def __init__(self, seconds: float, on_open=None, on_close=None):
        self.seconds = seconds
        self.on_open, self.on_close = on_open, on_close
        self.t0 = self.t1 = None
        self.e0 = self.epochs = None
        self.losses: list = []

    def __call__(self, row: dict) -> None:
        now = time.perf_counter()
        if self.t0 is None:
            self.t0, self.e0 = now, row["epoch"]
            if self.on_open:
                self.on_open()
            self.t0 = time.perf_counter()
            return
        self.losses.append(row["loss"])
        if now - self.t0 >= self.seconds:
            self.t1, self.epochs = now, row["epoch"] - self.e0
            if self.on_close:
                self.on_close()
            raise WindowClosed

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""
    workload: str
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    family: object                  # the models/<family>.py module
    peak: dict
    counts: dict
    in_dim: int
    out_dim: int
    epochs: int
    window_s: float
    compiles_in_window: int
    compile_s: float
    trace: object = None            # xplane.Reduction of the window

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def epoch_s(self) -> float:
        return self.window_s / self.epochs

    def device_ms_per_epoch(self, pattern) -> float | None:
        """Mean over chips of the device time of ops matching
        ``pattern``, per window epoch; ``None`` where none ran."""
        t = self.trace.op_seconds(pattern) if self.trace else None
        return None if t is None else 1e3 * t / self.epochs


def graph_arrays(cfg: dict, seed: int) -> dict:
    return load_module("graphs", cfg["graph"]["generator"]).generate(
        cfg["graph"], seed)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, config_overrides: dict | None = None,
        t_start: float | None = None, keep: dict | None = None) -> dict:
    """One run; returns the result line's object.

    ``require_chip=False`` with small ``config_overrides`` rehearses the
    whole path on the CPU: the result then carries the check and no
    metric, since no number from a CPU run is a device metric.  ``keep``
    receives the graph, the reference's and the program's readings.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load("workloads", workload)
    cfg = load("configs", cell["config"])
    for part, over in (config_overrides or {}).items():
        cfg[part] = {**cfg[part], **over}
    traffic = load("traffic", cell["traffic"])
    chips = traffic["chips"]
    model = cfg["model"]

    import jax
    devs = devices(chips, require_chip)
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import check
    import reference
    import work
    from repro.dist import ratectl
    from repro.core.varco import CommPolicy
    from repro.graph.data import GraphData
    from repro.graph.partition import PARTITIONERS
    from repro.train import trainer

    family = load_module("models", model["family"])
    fields = dict(family.program_fields(model))
    named = {k: fields.pop(k) for k in TRAIN_GNN_FIELDS}

    clock = CompileClock()
    arrays = graph_arrays(cfg, seed)
    n = len(arrays["indptr"]) - 1
    q = traffic["partitions"]
    g = GraphData(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], arrays["train_mask"], arrays["val_mask"],
                  arrays["test_mask"], name=workload)
    owner = PARTITIONERS[traffic["scheme"]](g, q, seed=seed)
    if not np.array_equal(owner[arrays["perm"]], np.arange(n) % q):
        raise RuntimeError("the partition drawn from the seed does not cut "
                           "the graph along its base partition: the "
                           "generator's premise no longer holds")
    counts = work.partition_counts(arrays["indptr"], arrays["indices"],
                                   owner, q)
    in_dim = arrays["features"].shape[1]
    out_dim = int(arrays["labels"].max()) + 1
    policy = CommPolicy.parse(traffic["policy"], traffic["horizon_epochs"])

    trace_dir = OUT / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    at_open = {}

    def on_open():
        at_open.update(compiles=clock.count, compile_s=clock.seconds,
                       setup_s=time.perf_counter() - t_start)
        if trace:
            jax.profiler.start_trace(str(trace_dir))

    window = Window(seconds, on_open=on_open,
                    on_close=jax.profiler.stop_trace if trace else None)
    recorder = StepRecorder(CHECKED_STEPS + 1)
    patches = [(trainer, "make_train_step",
                recorder.wrap(trainer.make_train_step)),
               (ratectl, "make_auto_train_step",
                recorder.wrap(ratectl.make_auto_train_step)),
               (trainer, "GNNConfig",
                functools.partial(trainer.GNNConfig, **fields))]
    saved = [getattr(mod, name) for mod, name, _ in patches]
    for mod, name, fn in patches:
        setattr(mod, name, fn)
    try:
        with jax.default_matmul_precision(model["matmul_precision"]):
            trainer.train_gnn(
                g, q=q, scheme=traffic["scheme"], policy=policy,
                epochs=traffic["horizon_epochs"], lr=model["optimizer"]["lr"],
                **named, seed=seed,
                eval_every=traffic["eval_every"], wire=traffic["wire"],
                use_shard_map=traffic["shard_map"], log_fn=window)
        raise RuntimeError("the job ended before the window closed")
    except WindowClosed:
        pass
    finally:
        for (mod, name, _), fn in zip(patches, saved):
            setattr(mod, name, fn)
    peak_bytes = memory_peak(devs)
    compiles_in_window = clock.count - at_open["compiles"]

    prog = recorder.readings(model["optimizer"]["b1"])
    del recorder
    gc.collect()
    ref = reference.train_readings(family, arrays, model, seed,
                                   CHECKED_STEPS, model["matmul_precision"])
    nums = check.numbers(prog, ref)
    if keep is not None:
        keep.update(arrays=arrays, model=model, family=family, owner=owner,
                    ref=ref, prog=prog, nums=nums)
    correct, shown = check.judge(nums, cell["limits"])
    losses = [float(x) for x in window.losses]
    failed = sum(not math.isfinite(x) for x in losses)

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct) and failed == 0,
              "attempted": window.epochs, "failed": failed,
              "metrics": {}, "device": device}
    if require_chip:
        r = Run(workload=workload, config=cfg, traffic=traffic, chips=chips,
                device_kind=dev.device_kind, family=family,
                peak=work.peak(dev.device_kind),
                counts=counts, in_dim=in_dim, out_dim=out_dim,
                epochs=window.epochs, window_s=window.wall_s,
                compiles_in_window=compiles_in_window,
                compile_s=at_open["compile_s"])
        if trace:
            import xplane
            r.trace = xplane.reduce(trace_dir, chips)
            device["busy_s"] = r.trace.busy_s
            device["window_s"] = window.wall_s
            result["metrics"] = per_layer_metrics(r)
            result["breakdown"] = r.trace.breakdown()
        else:
            result["metrics"] = {
                "setup_s": {"value": at_open["setup_s"], "unit": "s"},
                "epoch_s": {"value": r.epoch_s, "unit": "s"},
                "peak_hbm_gb": {"value": peak_bytes / 1e9, "unit": "GB"},
            }
    result["checks"] = shown
    return result


def per_layer_metrics(r: Run) -> dict:
    """Every reader under ``metrics/`` that finds something to read."""
    out = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        mod = load_module("metrics", path.stem)
        value = mod.read(r)
        if value is not None:
            out[path.stem] = {"value": value, "unit": mod.UNIT}
    return out
