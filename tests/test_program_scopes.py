"""The step named from inside: ``jax.named_scope`` in the compiled
superstep, the process's instruction -> scope registry
(obs/program_scopes.py), and the trainer's and boundary's spans as
``pbx:`` annotations and always-on totals (utils/trace.py)."""

from __future__ import annotations

import gc
import glob
import os
import re

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")

from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import DeepFM  # noqa: E402
from paddlebox_tpu.obs.program_scopes import (  # noqa: E402
    REGISTRY,
    ProgramRegistry,
    scope_map,
    scope_of,
)
from paddlebox_tpu.table import (  # noqa: E402
    HostSparseTable,
    SparseOptimizerConfig,
    ValueLayout,
)
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402
from paddlebox_tpu.utils.trace import PROFILER  # noqa: E402

S, D, B, N = 6, 8, 64, 1024  # the benchmark's toy: 6 slots x embedx 8
PROGRAM = f"superstep/train/8x{B}"
COMPILE = "/jax/core/compile/backend_compile_duration"

STEP_SCOPES = {
    "build_batch/offsets", "build_batch/ragged_rows", "build_batch/dedup_sort",
    "build_batch/dedup_scan", "build_batch/inverse_scatter",
    "pull/expand", "seqpool_cvm", "model", "loss", "nan_guard",
    "push/merge", "push/sparse_opt", "push/table_scatter", "dense_opt", "auc",
}


def _dataset(tmp_path):
    rng = np.random.default_rng(26)
    path = tmp_path / "part-000.txt"
    with open(path, "w") as f:
        for _ in range(N):
            keys = rng.integers(1, 5000, S)
            f.write(f"1 {float(rng.integers(0, 2))} " + " ".join(f"1 {k}" for k in keys) + "\n")
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
    )
    layout = ValueLayout(embedx_dim=D)
    opt = SparseOptimizerConfig(embedx_threshold=0.0)
    ds = BoxPSDataset(
        schema, HostSparseTable(layout, opt, n_shards=2, seed=0),
        batch_size=B, shuffle_mode="none",
    )
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    return ds, layout, opt


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One pass opened and 16 batches (two supersteps) trained, with every
    backend compile of the process listened to from before the build."""
    from jax._src import monitoring

    compiles, texts = [], {}

    def on_event(event, duration, **kw):
        if event == COMPILE:
            compiles.append(kw.get("fun_name"))

    def record(name, fun_name, hlo_text):  # keep the text the map was read from
        texts[name] = hlo_text
        return ProgramRegistry.record(REGISTRY, name, fun_name, hlo_text)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    REGISTRY.record = record
    try:
        ds, layout, opt = _dataset(tmp_path_factory.mktemp("scopes"))
        ds.begin_pass(round_to=8)
        cfg = TrainStepConfig(
            num_slots=S, batch_size=B, layout=layout, sparse_opt=opt,
            auc_buckets=1000, check_nan=True,
        )
        tr = CTRTrainer(
            DeepFM(num_slots=S, feat_width=layout.pull_width, embedx_dim=D, hidden=(32, 16)),
            cfg, dense_opt=optax.adam(1e-3),
        )
        tr.init_params(jax.random.PRNGKey(0))
        out = tr.train_pass(ds, n_batches=16)
        assert out["batches"] == 16
        yield {"ds": ds, "tr": tr, "compiles": compiles, "text": texts[PROGRAM]}
    finally:
        del REGISTRY.record
        monitoring.unregister_event_duration_listener(on_event)


def test_scope_of_strips_what_jax_adds_and_folds_the_backward_pass():
    body = "jit(superstep)/while/body/closed_call/"
    assert scope_of(body + "build_batch/inverse_scatter/scatter") == "build_batch/inverse_scatter"
    assert scope_of(body + "transpose(jvp(seqpool_cvm))/gather") == "seqpool_cvm"
    assert scope_of(body + "jvp(model)/tower/dot_general") == "model/tower"
    assert scope_of(body + "build_batch/ragged_rows/jit(searchsorted)/vmap()/while/body/gather") \
        == "build_batch/ragged_rows"
    # a constant's name ends in the scope or in a jit(...) token, not in a primitive
    assert scope_of(body + "jvp(loss)/jit(log_sigmoid)/jit(softplus)") == "loss"
    assert scope_of(body + "push/merge/mul;" + body + "add") == "push/merge"  # merged: the first
    assert scope_of("jit(superstep)/while/body/dynamic_update_slice") == ""
    assert scope_of("jit(superstep)/while") == "" and scope_of("") == ""
    text = (
        'ENTRY %main {\n'
        '  %p = f32[4]{0} parameter(0)\n'
        '  %fusion.7 = s32[8]{0:T(1024)} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(superstep)/while/body/closed_call/pull/expand/jit(_take)/gather" '
        'source_file="x.py" source_line=3}\n'
        '  ROOT tuple.1 = (s32[8]) tuple(%fusion.7)\n}\n'
    )
    assert scope_map(text) == {"p": "", "fusion.7": "pull/expand", "tuple.1": ""}


def test_superstep_map_holds_every_scope_and_names_the_sparse_instructions(trained):
    entry = REGISTRY.get(PROGRAM)
    assert entry is not None, REGISTRY.names()
    scopes = entry["scopes"]
    assert entry["instructions"] == len(scopes) > 100
    found = set(scopes.values())
    assert STEP_SCOPES <= found, STEP_SCOPES - found
    # the push reads again the rows the pull gathered: XLA keeps one gather, under either name
    assert {"pull/table_gather", "push/table_gather"} & found
    # every sort, scatter (a segment sum is one) and gather of the step body is in a scope
    sparse = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s(sort|scatter|gather)\(",
                        trained["text"], re.M)
    assert len(sparse) >= 10 and {op for _, op in sparse} == {"sort", "scatter", "gather"}
    assert not [(n, op) for n, op in sparse if not scopes[n]]
    # jax's own build seconds ride along, by the jitted function's name
    assert entry["fun_name"] == "superstep"
    assert entry["lower_s"] > 0 and entry["compile_s"] > 0 and entry["trace_s"] > 0


def test_ragged_rows_searches_nothing_in_the_compiled_superstep(trained):
    """A batch's segments come from a scatter of segment starts and a prefix
    sum: no loop of its own (a binary search is a ``while``), nothing born of
    ``searchsorted``, and one per-id gather, that of the row ids."""
    scopes = REGISTRY.get(PROGRAM)["scopes"]
    ops = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?[\s)]([a-z][\w\-]*)\(", trained["text"], re.M)
    assert len(ops) == len(scopes)
    ragged = [(n, op) for n, op in ops if scopes[n].startswith("build_batch/ragged_rows")]
    kinds = {op for _, op in ragged}
    assert len(ragged) > 10 and "scatter" in kinds, kinds
    # the scan over the batches is the program's only loop, outside every scope
    loops = [n for n, op in ops if op == "while"]
    assert loops and not [n for n in loops if scopes[n]], [(n, scopes[n]) for n in loops]
    assert "searchsorted" not in trained["text"]
    assert {scopes[n] for n, op in ragged if op == "gather"} == {"build_batch/ragged_rows/row_gather"}
    assert {"build_batch/ragged_rows/segment_scan", "build_batch/ragged_rows/row_gather"} <= set(scopes.values())


def test_a_profiler_trace_holds_the_trainers_spans_as_pbx_annotations(trained, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        trained["tr"].train_pass(trained["ds"], n_batches=16)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for ln in plane.lines for e in ln.events
            if e.name.startswith("pbx:")}
    assert {"pbx:train_pass.open", "pbx:resident_prepare", "pbx:superstep_dispatch",
            "pbx:superstep_wait", "pbx:superstep_consume", "pbx:train_pass.tail"} <= host, host


def test_totals_count_every_span_with_the_profiler_disabled(tmp_path):
    assert not PROFILER.enabled
    PROFILER.reset()
    with PROFILER.record_event("stage") as span:
        pass
    with PROFILER.record_event("stage"):
        pass
    assert span.seconds > 0
    tot = PROFILER.totals()["stage"]
    assert tot["count"] == 2 and tot["seconds"] >= span.seconds
    assert PROFILER.export_chrome_trace(str(tmp_path / "t.json")) == 0  # the ring stayed empty
    # a boundary stage publishes its span's own length under its literal stat name
    ds, _, _ = _dataset(tmp_path)
    ds.begin_pass(round_to=8)
    tot = PROFILER.totals()
    for stage in ("dedup", "pull"):
        assert tot[f"boundary.{stage}"]["count"] == 1
        assert STAT_GET(f"boundary.{stage}_s") == tot[f"boundary.{stage}"]["seconds"] > 0
    assert tot["boundary.layout"]["count"] == 1


def test_recording_the_map_compiles_nothing_and_outlives_the_trainer(trained):
    # two supersteps ran and the map was recorded: the program compiled once
    assert trained["compiles"].count("jit(superstep)") == 1, trained["compiles"]
    n = REGISTRY.get(PROGRAM)["instructions"]
    trained.pop("tr")
    trained.pop("ds")
    gc.collect()
    jax.clear_caches()
    gc.collect()
    entry = REGISTRY.get(PROGRAM)
    assert entry["instructions"] == n and "build_batch/dedup_sort" in entry["scopes"].values()


# ---- under jax.checkpoint and a scan over layers (a language model's step) ----

def _parent_scope_of(op_name: str) -> str:
    """``scope_of`` as it was before it folded recomputed bodies."""
    from paddlebox_tpu.obs import program_scopes as ps

    first = op_name.split(";", 1)[0]
    tokens = [t for t in ps._WRAPPER.sub("", ps._PROGRAM.sub("jit", first)).split("/") if t]
    if tokens and tokens[-1] != "jit":
        tokens.pop()
    return "/".join(t for t in tokens if t not in ps._STRUCTURAL - {"rematted_computation"})


def test_scope_of_folds_recomputed_and_scanned_bodies_to_one_path():
    body = "jit(superstep)/while/body/closed_call/"
    # forward, inside the layer scan; an einsum's own spec is no scope
    assert scope_of(body + "jvp(model/mla/scores)/while/body/checkpoint/bqhd,bkhd->bhqk/dot_general") \
        == "model/mla/scores"
    # the backward pass re-traces a checkpointed body under the path of its call
    assert scope_of(body + "transpose(jvp(model/mla/scores))/checkpoint/rematted_computation/"
                    "model/mla/scores/bhqk,bkhd->bqhd/dot_general") == "model/mla/scores"
    assert scope_of(body + "transpose(jvp(model/mtp/moe/shared))/while/body/checkpoint/"
                    "rematted_computation/model/mtp/moe/experts/dot_general") == "model/mtp/moe/experts"
    assert scope_of(body + "loss/head/while/body/checkpoint/rematted_computation/loss/head/"
                    "reduce_max") == "loss/head"
    # what has no repeat stays whole
    assert scope_of(body + "push/merge/mul") == "push/merge"
    assert scope_of(body + "build_batch/ragged_rows/segment_scan/scatter-add") \
        == "build_batch/ragged_rows/segment_scan"


# what the fused attention's nine custom calls carry in the token cell's compiled
# superstep (copied from the chip's HLO, PR 29: the dense layer, the scanned
# expert layers and the MTP module, each forward, recomputed under its
# checkpoint and backward): the kernel's ``name=`` is the scope ``pallas_call``
# itself opens around the primitive, and is no scope of the program's
_STEP = "jit(superstep)/while/body/closed_call/"
KERNEL_OP_NAMES = {
    "model/mla/scores": [
        _STEP + "jvp(model/mla/scores)/causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp(jvp()))/checkpoint/rematted_computation/model/mla/scores/"
        "causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp(jvp()))/checkpoint/model/mla/scores/causal_attention_bwd/pallas_call",
        _STEP + "jvp()/while/body/closed_call/model/mla/scores/causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/"
        "model/mla/scores/causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp())/while/body/closed_call/checkpoint/model/mla/scores/"
        "causal_attention_bwd/pallas_call",
    ],
    "model/mtp/mla/scores": [
        _STEP + "jvp(model/mtp/mla/scores)/causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp(jvp()))/checkpoint/rematted_computation/model/mtp/mla/scores/"
        "causal_attention_fwd/pallas_call",
        _STEP + "transpose(jvp(jvp()))/checkpoint/model/mtp/mla/scores/causal_attention_bwd/pallas_call",
    ],
}


@pytest.mark.parametrize("scope", sorted(KERNEL_OP_NAMES))
def test_a_named_kernels_custom_call_lands_in_the_scope_it_was_called_in(scope):
    for op_name in KERNEL_OP_NAMES[scope]:
        assert scope_of(op_name) == scope, op_name
    # an unnamed kernel called under a jitted wrapper and a primitive that merely
    # follows a scope keep every level
    assert scope_of("jit(superstep)/model/mla/scores/jit(causal_attention)/pallas_call") \
        == "model/mla/scores"
    assert scope_of("jit(superstep)/model/mla/scores/causal_attention_fwd/mul") \
        == "model/mla/scores/causal_attention_fwd"


def test_the_ctr_supersteps_scopes_are_what_they_were(trained):
    names = re.findall(r'op_name="([^"]*)"', trained["text"])
    assert len(names) > 100
    assert [scope_of(n) for n in names] == [_parent_scope_of(n) for n in names]
    found = set(REGISTRY.get(PROGRAM)["scopes"].values())
    assert not [s for s in found if "remat" in s or "->" in s or s.startswith("model/")], found


GLM_SCOPES = {
    f"{pre}/{leaf}" for pre in ("model", "model/mtp")
    for leaf in ("mla/q_proj", "mla/kv_proj", "mla/rope", "mla/scores", "mla/out_proj",
                 "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared")
} | {"model/dense_mlp", "model/mtp/eh_proj", "loss/head"}


def test_a_language_models_superstep_maps_every_layer_scope_under_checkpoint_and_scan(tmp_path):
    from test_glm_moe_lite import B as GB, H, T, TINY, _dataset as token_dataset, _token_files, _trainer

    from benchmark.reference import glm_moe_lite as ref

    ids = np.random.default_rng(2).integers(0, 64, (16, T))
    box, ds = token_dataset(_token_files(tmp_path, ids))
    tr = _trainer(box, ref.init(jax.random.PRNGKey(1), TINY, 3 + H))
    assert tr.train_pass(ds, n_batches=8)["batches"] == 8
    entry = REGISTRY.get(f"superstep/train/8x{GB}")
    found = set(entry["scopes"].values())
    assert GLM_SCOPES <= found, GLM_SCOPES - found
    # nothing else under model/ or loss/: no doubled prefix, no structural name, no einsum spec
    ours = {s for s in found if s.split("/")[0] in ("model", "loss", "mla", "moe", "mtp")}
    assert ours == GLM_SCOPES, ours - GLM_SCOPES
    shared = {s for s in STEP_SCOPES if s.split("/")[0] in ("build_batch", "pull", "push")} | {"dense_opt"}
    assert shared <= found and not {"seqpool_cvm", "auc", "loss"} & found
    # forward, recomputed and backward instructions all carry the scope: the
    # scores' scope holds several dots (QK^T and PV, each of the three passes)
    text_ops = [n for n, s in entry["scopes"].items() if s == "model/mla/scores"]
    assert len(text_ops) >= 6
