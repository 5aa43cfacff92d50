"""What window drivers of training cells share: opening a pass through the
program's own entry points, the first superstep from the seed's state with
what the comparison needs read off it, that comparison, and the bracketed
profiler trace."""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.utils import native
from paddlebox_tpu.utils.monitor import STAT_GET

from benchmark import gen, trace_reduce

SAMPLE_ROWS = 65_536
SAMPLE_HOT = 4_096


def scan_batches() -> int:
    return int(config.get_flag("resident_scan_batches"))


class Tracer:
    """One profiler trace bracketed by ``start``/``stop``; host spans named
    ``names`` are open for its whole length (the calls they stand for were
    entered before the trace began, where an annotation is not recorded)."""

    def __init__(self, names=("traced",)):
        self.names = names
        self.dir: Optional[str] = None
        self._open = []

    def start(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        for n in self.names:
            ann = jax.profiler.TraceAnnotation(f"bench:{n}")
            ann.__enter__()
            self._open.append(ann)

    def stop(self) -> None:
        while self._open:
            self._open.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()

    def load(self) -> dict:
        try:
            return trace_reduce.load(trace_reduce.find_trace(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def generate(cell: dict, rec):
    """The pass's files in a temporary directory, with the arrays they were
    written from."""
    work = tempfile.mkdtemp(prefix="bench_data_")
    with rec.span("generate"):
        files, keys, labels = gen.make_pass(work, cell["mix"], cell["cfg"]["num_slots"],
                                            cell["seed"])
    return work, files, keys, labels


def check_native() -> None:
    if not native.available() or STAT_GET("native.build_failures"):
        raise AssertionError("native tier (csrc/) did not build or load")


def open_pass(ds, files, rec, prefix: str = "") -> None:
    """set_filelist -> load_into_memory -> begin_pass, each under its span."""
    with rec.span("load"):
        ds.set_filelist(files)
        ds.load_into_memory()
    with rec.span(prefix + "begin_pass"):
        ds.begin_pass()
    if ds.store is None:
        raise AssertionError("native tier not loaded: the dataset fell back to the Python parser")


def sample_keys(b_keys: np.ndarray) -> np.ndarray:
    """The keys whose rows are compared, sorted: the batches' most frequent
    keys (touched by every step, many times) and every n-th of the rest."""
    uniq, counts = np.unique(b_keys, return_counts=True)
    hot = np.zeros(len(uniq), bool)
    hot[np.argsort(-counts, kind="stable")[:SAMPLE_HOT]] = True
    rest = np.flatnonzero(~hot)
    hot[rest[::max(1, len(rest) // SAMPLE_ROWS)]] = True
    return uniq[hot]


def first_superstep(cell: dict, ds, trainer, keys: np.ndarray, labels: np.ndarray,
                    weights, rec) -> dict:
    """Drive the window's own call through its first superstep from the
    seed's state and read off what the comparison needs: the K batches the
    program fed (as the generator's records), a sample of their keys with the
    program's rows before and after, each step's loss, the dense leaves and
    Adam's first moment."""
    K, W = scan_batches(), ds.table.layout.width
    idx = np.stack(list(ds.batch_indices(K)))  # [K, B] store records = file order
    b_keys, b_labels = keys[idx], labels[idx]
    sample = sample_keys(b_keys)
    pos = np.searchsorted(ds.ws.sorted_keys, sample)
    if not np.array_equal(ds.ws.sorted_keys[np.minimum(pos, ds.ws.n_keys - 1)], sample):
        raise AssertionError("keys of the generated batches are missing from the pass's working set")
    rows = ds.ws.row_of_sorted[pos]
    open_rows = np.asarray(ds.device_table).reshape(-1, W)[rows]
    losses = []
    with rec.span("first_superstep"):
        out = trainer.train_pass(ds, n_batches=K, on_batch=lambda i, m: losses.append(m["loss"]))
        jax.block_until_ready(trainer.trained_table_device())
    if out["batches"] != K or out["nan_batches"]:
        raise AssertionError(f"first superstep: {out}")
    table = trainer.trained_table_device().reshape(-1, W)
    host = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)  # noqa: E731
    return {
        "keys": b_keys, "labels": b_labels, "sample_keys": sample, "sample_rows": rows,
        "prog": {
            "losses": np.asarray([float(x) for x in losses], np.float64),
            "open_rows": open_rows,
            "rows": np.asarray(table[jax.numpy.asarray(rows)]),
            "open_params": host(weights),
            "params": host(trainer.params),
            "mu": host(trainer.opt_state[0].mu),
        },
    }


def check_first_superstep(cell: dict, first: dict, weights) -> dict:
    """The plain reference over the batches of the first superstep, against
    what the program left: the numbers to judge. Runs on the device, so only
    once the program's state is freed."""
    from benchmark import compare, program
    from benchmark.reference import step as ref_step

    _, ref, _ = program.kind_modules(cell["cfg"])
    with jax.default_matmul_precision("highest"):
        out = ref_step.run_steps(
            ref.forward, weights, cell["cfg"], cell["seed"],
            first["keys"], first["labels"], first["sample_keys"])
    return compare.gaps(first["prog"], out, cell["cfg"])


def distinct_rows_per_step(b_keys: np.ndarray) -> float:
    return float(np.mean([len(np.unique(k)) for k in b_keys]))


def memory_peak_bytes() -> int:
    """Peak on the fullest chip; 0 where the backend keeps no count (the CPU
    of a rehearsal)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    return max(int(s["peak_bytes_in_use"]) if s else 0 for s in stats)


def warm_pass_tail(n_batches: int) -> None:
    """train_pass ends by stacking its n per-batch losses and taking their
    mean: two small eager programs whose shape is n. Build them in set-up, so
    that the window of n batches compiles nothing."""
    # committed to the device, as a step's outputs are: it is part of the key
    zero = jax.device_put(jax.numpy.zeros((), jax.numpy.float32), jax.devices()[0])
    float(jax.numpy.mean(jax.numpy.stack([zero] * n_batches)))


def release() -> None:
    """Once the driver has returned and its objects are out of reach: free
    the program's device state before the reference runs on the device."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def timed_train(trainer, ds, calls, on_batch=None):
    """``train_pass`` once for each batch count in ``calls`` (every call
    starts at the pass's first batch), closed by the trained table being
    ready. Returns the calls' results and the start and end times."""
    t0 = time.perf_counter()
    outs = [trainer.train_pass(ds, n_batches=n, on_batch=on_batch) for n in calls]
    jax.block_until_ready(trainer.trained_table_device())
    return outs, t0, time.perf_counter()
