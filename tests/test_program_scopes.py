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

    def record(name, fun_name, hlo_text, **kw):  # keep the text the map was read from
        texts[name] = hlo_text
        return ProgramRegistry.record(REGISTRY, name, fun_name, hlo_text, **kw)

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


def test_a_language_models_superstep_maps_every_layer_scope_under_checkpoint_and_scan(tmp_path, monkeypatch):
    from test_glm_moe_lite import B as GB, H, T, TINY, _dataset as token_dataset, _token_files, _trainer

    from benchmark.reference import glm_moe_lite as ref

    sstep_text = {}

    def record(name, fun_name, hlo_text, **kw):  # keep the text the map was read from
        sstep_text[name] = hlo_text
        return ProgramRegistry.record(REGISTRY, name, fun_name, hlo_text, **kw)

    ids = np.random.default_rng(2).integers(0, 64, (16, T))
    box, ds = token_dataset(_token_files(tmp_path, ids))
    tr = _trainer(box, ref.init(jax.random.PRNGKey(1), TINY, 3 + H))
    monkeypatch.setattr(REGISTRY, "record", record)
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
    # what the scopes leave out: the layer scan reads its weights out of their
    # stacks and writes its residuals into theirs, for the model's own scopes
    text = sstep_text[f"superstep/train/8x{GB}"]
    assert entry["scopes"] == scope_map(text)
    ins = _accounted(entry, text)
    slices = {n: serves for n, (kind, serves) in entry["unscoped"].items() if kind == "stack"}
    assert len(slices) >= 4, entry["unscoped"]
    served = {s for s in slices.values() if s.startswith("model/")}
    assert len(served) >= 3 and served <= GLM_SCOPES, slices
    assert entry["memory"]["temp_bytes"] > 0


# ---- what the scopes leave out: a kind and the scope it serves ----

# hand-written in the spelling of the TPU's compiled text (PR 36's supersteps on
# the chip): a layer scan's body that reads its weights and the kept ``o`` out of
# their stacks and writes ``x`` into its own, a prefix sum's expansion beside
# ``ragged_rows``, and the copies layout assignment put around them
_BODY = "jit(superstep)/while/body/closed_call/"
_WINDOW = ('backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[],'
           '"output_window_bounds":["1","2"],"estimated_cycles":"400"}}')
TPU_TEXT = f'''HloModule jit_superstep, is_scheduled=true, input_output_alias={{ {{0}}: (0, {{}}, may-alias) }}

%fused_computation.1.clone (param_0.1: f32[4,8,16], param_1.1: s32[], param_2.1: f32[8,16]) -> f32[4,8,16] {{
  %param_0.1 = f32[4,8,16]{{2,1,0:T(8,128)}} parameter(0)
  %param_2.1 = f32[8,16]{{1,0:T(8,128)}} parameter(2)
  %bitcast.1 = f32[1,8,16]{{2,1,0:T(8,128)}} bitcast(%param_2.1)
  %param_1.1 = s32[]{{:T(128)}} parameter(1)
  %constant.1 = s32[]{{:T(128)}} constant(0)
  ROOT %dynamic-update-slice.1 = f32[4,8,16]{{2,1,0:T(8,128)}} dynamic-update-slice(%param_0.1, %bitcast.1, %param_1.1, %constant.1, /*index=4*/%constant.1)
}}

%fused_computation.2.clone (param_0.2: f32[4,8,16], param_1.2: s32[]) -> f32[8,16] {{
  %param_0.2 = f32[4,8,16]{{2,1,0:T(8,128)}} parameter(0)
  %param_1.2 = s32[]{{:T(128)}} parameter(1)
  %constant.2 = s32[]{{:T(128)}} constant(3)
  %subtract.2 = s32[]{{:T(128)}} subtract(%constant.2, %param_1.2)
  %constant.3 = s32[]{{:T(128)}} constant(0)
  %dynamic-slice.2 = f32[1,8,16]{{2,1,0:T(8,128)}} dynamic-slice(%param_0.2, %subtract.2, %constant.3, %constant.3), dynamic_slice_sizes={{1,8,16}}
  ROOT %bitcast.2 = f32[8,16]{{1,0:T(8,128)}} bitcast(%dynamic-slice.2)
}}

%fused_computation.3 (param_0.3: f32[4,16,16], param_1.3: s32[]) -> bf16[16,16] {{
  %param_0.3 = f32[4,16,16]{{2,1,0:T(8,128)}} parameter(0)
  %param_1.3 = s32[]{{:T(128)}} parameter(1)
  %constant.4 = s32[]{{:T(128)}} constant(0)
  %dynamic-slice.3 = f32[1,16,16]{{2,1,0:T(8,128)}} dynamic-slice(%param_0.3, %param_1.3, %constant.4, %constant.4), dynamic_slice_sizes={{1,16,16}}
  %convert.3 = bf16[1,16,16]{{2,1,0:T(8,128)(2,1)}} convert(%dynamic-slice.3)
  ROOT %bitcast.3 = bf16[16,16]{{1,0:T(8,128)(2,1)}} bitcast(%convert.3)
}}

%fused_computation.4 (param_0.4: f32[8,16]) -> bf16[16,8] {{
  %param_0.4 = f32[8,16]{{1,0:T(8,128)}} parameter(0)
  %convert.4 = bf16[8,16]{{1,0:T(8,128)(2,1)}} convert(%param_0.4)
  %transpose.4 = bf16[16,8]{{0,1:T(8,128)(2,1)}} transpose(%convert.4), dimensions={{1,0}}
  ROOT %bitcast.4 = bf16[16,8]{{1,0:T(8,128)(2,1)}} bitcast(%transpose.4)
}}

%fused_computation.5 (param_0.5: f32[4,8,16], param_1.5: s32[], param_2.5: f32[8,16]) -> f32[4,8,16] {{
  %param_0.5 = f32[4,8,16]{{2,1,0:T(8,128)}} parameter(0)
  %param_1.5 = s32[]{{:T(128)}} parameter(1)
  %constant.5 = s32[]{{:T(128)}} constant(0)
  %dynamic-slice.5 = f32[1,8,16]{{2,1,0:T(8,128)}} dynamic-slice(%param_0.5, %param_1.5, %constant.5, %constant.5), dynamic_slice_sizes={{1,8,16}}
  %param_2.5 = f32[8,16]{{1,0:T(8,128)}} parameter(2)
  %bitcast.5 = f32[1,8,16]{{2,1,0:T(8,128)}} bitcast(%param_2.5)
  %add.5 = f32[1,8,16]{{2,1,0:T(8,128)}} add(%dynamic-slice.5, %bitcast.5)
  ROOT %dynamic-update-slice.5 = f32[4,8,16]{{2,1,0:T(8,128)}} dynamic-update-slice(%param_0.5, %add.5, %param_1.5, %constant.5, %constant.5)
}}

%region_3.5 (reduce_window_sum.11: s32[], reduce_window_sum.12: s32[]) -> s32[] {{
  %reduce_window_sum.11 = s32[]{{:T(128)}} parameter(0)
  %reduce_window_sum.12 = s32[]{{:T(128)}} parameter(1)
  ROOT %add.11 = s32[]{{:T(128)}} add(%reduce_window_sum.11, %reduce_window_sum.12)
}}

%fused_computation.6.clone (param_0.6: s32[64,128]) -> s32[64,128] {{
  %param_0.6 = s32[64,128]{{1,0:T(8,128)}} parameter(0)
  %constant.6 = s32[]{{:T(128)}} constant(0)
  ROOT %reduce-window.6 = s32[64,128]{{1,0:T(8,128)}} reduce-window(%param_0.6, %constant.6), window={{size=1x128 pad=0_0x127_0}}, to_apply=%region_3.5
}}

%fused_computation.7 (param_0.7: s32[64,128], param_1.7: s32[64]) -> s32[8192] {{
  %param_0.7 = s32[64,128]{{1,0:T(8,128)}} parameter(0)
  %param_1.7 = s32[64]{{0:T(128)}} parameter(1)
  %broadcast.7 = s32[64,128]{{1,0:T(8,128)}} broadcast(%param_1.7), dimensions={{0}}
  %add.7 = s32[64,128]{{1,0:T(8,128)}} add(%param_0.7, %broadcast.7)
  ROOT %bitcast.7 = s32[8192]{{0:T(1024)}} bitcast(%add.7)
}}

%layer_body (arg_tuple.1: (s32[], f32[8,16], f32[4,8,16], f32[4,8,16], f32[4,16,16], s32[8192])) -> (s32[], f32[8,16], f32[4,8,16], f32[4,8,16], f32[4,16,16], s32[8192]) {{
  %arg_tuple.1 = (s32[]{{:T(128)}}, f32[8,16]{{1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,16,16]{{2,1,0:T(8,128)}}, /*index=5*/s32[8192]{{0:T(1024)}}) parameter(0)
  %get-tuple-element.1 = s32[]{{:T(128)}} get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2 = f32[8,16]{{1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=1
  %get-tuple-element.3 = f32[4,8,16]{{2,1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=2
  %get-tuple-element.4 = f32[4,8,16]{{2,1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=3
  %get-tuple-element.5 = f32[4,16,16]{{2,1,0:T(8,128)}} get-tuple-element(%arg_tuple.1), index=4
  %get-tuple-element.6 = s32[8192]{{0:T(1024)}} get-tuple-element(%arg_tuple.1), index=5
  %bitcast_dynamic-update-slice_fusion.23 = f32[4,8,16]{{2,1,0:T(8,128)}} fusion(%get-tuple-element.3, %get-tuple-element.1, %get-tuple-element.2), kind=kLoop, calls=%fused_computation.1.clone, metadata={{op_name="jit(superstep)/while/body/closed_call/jvp()/while/body/dynamic_update_slice" stack_frame_id=64}}, {_WINDOW}
  %dynamic-slice_bitcast_fusion.25 = f32[8,16]{{1,0:T(8,128)}} fusion(%get-tuple-element.4, %get-tuple-element.1), kind=kLoop, calls=%fused_computation.2.clone, metadata={{op_name="jit(superstep)/while/body/closed_call/transpose(jvp())/while/body/dynamic_slice" stack_frame_id=64}}, {_WINDOW}
  %fusion.577 = bf16[16,16]{{1,0:T(8,128)(2,1)}} fusion(%get-tuple-element.5, %get-tuple-element.1), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{_BODY}jvp()/while/body/closed_call/convert_element_type" stack_frame_id=245}}, {_WINDOW}
  %convert_bitcast_fusion = bf16[16,8]{{1,0:T(8,128)(2,1)}} fusion(%dynamic-slice_bitcast_fusion.25), kind=kLoop, calls=%fused_computation.4, {_WINDOW}
  %copy.422 = bf16[16,8]{{0,1:T(8,128)(2,1)}} copy(%convert_bitcast_fusion)
  %bitcast.30 = bf16[128]{{0:T(1024)(2,1)}} bitcast(%copy.422)
  %fusion.40 = f32[8,16]{{1,0:T(8,128)}} fusion(%get-tuple-element.2, %fusion.577), kind=kOutput, calls=%fused_computation.8, metadata={{op_name="{_BODY}jvp()/while/body/closed_call/model/attn/qkv_proj/dot_general" stack_frame_id=300}}
  %fusion.41 = f32[8,16]{{1,0:T(8,128)}} fusion(%fusion.40, %bitcast.30), kind=kOutput, calls=%fused_computation.9, metadata={{op_name="{_BODY}transpose(jvp())/while/body/closed_call/checkpoint/model/attn/scores_full/mul" stack_frame_id=301}}
  %fusion.42 = f32[8,16]{{1,0:T(8,128)}} fusion(%fusion.41, %bitcast.30), kind=kOutput, calls=%fused_computation.10, metadata={{op_name="{_BODY}transpose(jvp())/while/body/closed_call/checkpoint/model/attn/out_proj/dot_general" stack_frame_id=302}}
  %copy.293 = f32[8,16]{{0,1:T(8,128)}} copy(%get-tuple-element.2)
  %copy.294 = f32[8,16]{{1,0:T(8,128)}} copy(%copy.293)
  %bitcast_dynamic-update-slice_fusion.24 = f32[4,8,16]{{2,1,0:T(8,128)}} fusion(%get-tuple-element.4, %get-tuple-element.1, %fusion.42), kind=kLoop, calls=%fused_computation.1.clone, metadata={{op_name="jit(superstep)/while/body/closed_call/jvp()/while/body/dynamic_update_slice" stack_frame_id=64}}, {_WINDOW}
  %add_dynamic-update-slice_fusion = f32[4,8,16]{{2,1,0:T(8,128)}} fusion(%bitcast_dynamic-update-slice_fusion.24, %get-tuple-element.1, %copy.294), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(superstep)/while/body/closed_call/transpose(jvp())/while/body/add_any" stack_frame_id=64}}
  %bitcast.31 = s32[64,128]{{1,0:T(8,128)}} bitcast(%get-tuple-element.6)
  %reduce-window_fusion.6 = s32[64,128]{{1,0:T(8,128)}} fusion(%bitcast.31), kind=kLoop, calls=%fused_computation.6.clone, {_WINDOW}
  %slice_reduce_fusion = s32[64]{{0:T(128)}} fusion(%reduce-window_fusion.6), kind=kLoop, calls=%fused_computation.11, metadata={{op_name="{_BODY}build_batch/ragged_rows/segment_scan/cumsum" stack_frame_id=90}}
  %add_bitcast_fusion.7 = s32[8192]{{0:T(1024)}} fusion(%reduce-window_fusion.6, %slice_reduce_fusion), kind=kLoop, calls=%fused_computation.7
  %fusion.50 = s32[8192]{{0:T(1024)}} fusion(%add_bitcast_fusion.7), kind=kLoop, calls=%fused_computation.12, metadata={{op_name="{_BODY}build_batch/ragged_rows/row_gather/gather" stack_frame_id=91}}
  %constant.9 = s32[]{{:T(128)}} constant(1)
  %add.9 = s32[]{{:T(128)}} add(%get-tuple-element.1, %constant.9), metadata={{op_name="jit(superstep)/while/body/closed_call/jvp()/while/body/add" stack_frame_id=64}}
  ROOT %tuple.9 = (s32[]{{:T(128)}}, f32[8,16]{{1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,16,16]{{2,1,0:T(8,128)}}, /*index=5*/s32[8192]{{0:T(1024)}}) tuple(%add.9, %copy.294, %bitcast_dynamic-update-slice_fusion.23, %add_dynamic-update-slice_fusion, %get-tuple-element.5, /*index=5*/%fusion.50)
}}

%layer_cond (arg_tuple.2: (s32[], f32[8,16], f32[4,8,16], f32[4,8,16], f32[4,16,16], s32[8192])) -> pred[] {{
  %arg_tuple.2 = (s32[]{{:T(128)}}, f32[8,16]{{1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,16,16]{{2,1,0:T(8,128)}}, /*index=5*/s32[8192]{{0:T(1024)}}) parameter(0)
  %get-tuple-element.20 = s32[]{{:T(128)}} get-tuple-element(%arg_tuple.2), index=0
  %constant.20 = s32[]{{:T(128)}} constant(4)
  ROOT %compare.20 = pred[]{{:T(512)}} compare(%get-tuple-element.20, %constant.20), direction=LT
}}

ENTRY %main.282 (state_x.1: f32[8,16], state_w.1: f32[4,16,16], ids.1: s32[8192]) -> (f32[8,16], s32[8192]) {{
  %state_x.1 = f32[8,16]{{1,0:T(8,128)}} parameter(0)
  %state_w.1 = f32[4,16,16]{{2,1,0:T(8,128)}} parameter(1)
  %ids.1 = s32[8192]{{0:T(1024)}} parameter(2)
  %constant.30 = s32[]{{:T(128)}} constant(0)
  %broadcast.5601 = f32[4,8,16]{{2,1,0:T(8,128)}} broadcast(%constant.30), dimensions={{}}
  %copy.118 = f32[4,16,16]{{1,2,0:T(8,128)}} copy(%state_w.1)
  %tuple.30 = (s32[]{{:T(128)}}, f32[8,16]{{1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,16,16]{{2,1,0:T(8,128)}}, /*index=5*/s32[8192]{{0:T(1024)}}) tuple(%constant.30, %state_x.1, %broadcast.5601, %broadcast.5601, %copy.118, /*index=5*/%ids.1)
  %while.803 = (s32[]{{:T(128)}}, f32[8,16]{{1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,8,16]{{2,1,0:T(8,128)}}, f32[4,16,16]{{2,1,0:T(8,128)}}, /*index=5*/s32[8192]{{0:T(1024)}}) while(%tuple.30), condition=%layer_cond, body=%layer_body, metadata={{op_name="jit(superstep)/while" stack_frame_id=2}}
  %get-tuple-element.30 = f32[8,16]{{1,0:T(8,128)}} get-tuple-element(%while.803), index=1
  %get-tuple-element.31 = s32[8192]{{0:T(1024)}} get-tuple-element(%while.803), index=5
  %copy-start = (f32[8,16]{{1,0:T(8,128)S(1)}}, f32[8,16]{{1,0:T(8,128)}}, u32[]{{:S(2)}}) copy-start(%get-tuple-element.30)
  %copy-done = f32[8,16]{{1,0:T(8,128)S(1)}} copy-done(%copy-start)
  %fusion.60 = f32[8,16]{{1,0:T(8,128)}} fusion(%copy-done), kind=kLoop, calls=%fused_computation.13, metadata={{op_name="jit(superstep)/dense_opt/mul" stack_frame_id=400}}
  %copy.500 = f32[8,16]{{0,1:T(8,128)}} copy(%fusion.60)
  ROOT %tuple.31 = (f32[8,16]{{0,1:T(8,128)}}, s32[8192]{{0:T(1024)}}) tuple(%copy.500, %get-tuple-element.31)
}}
'''
UNSCOPED_ON_THE_TPU = {
    # a forward scan's output written into its stack feeds only the loop's root
    # tuple; here no loop reads that stack, and what it stacks is the loop's own
    # parameter: nobody's
    "bitcast_dynamic-update-slice_fusion.23": ["stack", ""],
    # the kept ``o`` read out of its stack, for the cast that feeds the scores' backward
    "dynamic-slice_bitcast_fusion.25": ["stack", "model/attn/scores_full"],
    # a weight read out of its stack and cast on the way: the cast rides along
    "fusion.577": ["stack", "model/attn/qkv_proj"],
    # a cast and a transpose in one fusion, no metadata at all; through a copy
    # and a bitcast two scoped users are as near: the first in the text
    "convert_bitcast_fusion": ["cast", "model/attn/scores_full"],
    "copy.422": ["copy", "model/attn/scores_full"],
    # the carried ``x`` in another layout and back feeds the root tuple alone:
    # the loop's next turn reads it at the same index, for the first product
    "copy.293": ["copy", "model/attn/qkv_proj"],
    "copy.294": ["copy", "model/attn/qkv_proj"],
    # written into a stack whose slices the next turn reads for the scores
    "bitcast_dynamic-update-slice_fusion.24": ["stack", "model/attn/scores_full"],
    # a stack that is added into is arithmetic on floats
    "add_dynamic-update-slice_fusion": ["other", "model/attn/scores_full"],
    # the prefix sum's expansion lost the scope it was written in; its users kept theirs
    "reduce-window_fusion.6": ["other", "build_batch/ragged_rows/segment_scan"],
    "add_bitcast_fusion.7": ["other", "build_batch/ragged_rows/row_gather"],
    # jax's own names only: the loop counter serves what it first indexes
    "add.9": ["other", "model/attn/qkv_proj"],
    # the entry. Zeros for the two stacks and a layout copy of the stacked weights
    # go into the loop as elements 2 to 4 of its tuple and serve what reads those
    # elements in the body, not what reads the loop's other results
    "broadcast.5601": ["other", "model/attn/scores_full"],
    "copy.118": ["copy", "model/attn/qkv_proj"],
    # an asynchronous copy into fast memory ahead of Adam
    "copy-start": ["copy", "dense_opt"],
    "copy-done": ["copy", "dense_opt"],
    # nothing uses it but the program's result: by what it was made from
    "copy.500": ["copy", "dense_opt"],
}


@pytest.mark.parametrize("instruction", sorted(UNSCOPED_ON_THE_TPU))
def test_an_unscoped_instruction_gets_its_kind_and_the_scope_it_serves(instruction):
    from paddlebox_tpu.obs.program_scopes import unscoped_map

    assert unscoped_map(TPU_TEXT)[instruction] == UNSCOPED_ON_THE_TPU[instruction]


def test_the_account_lists_what_can_be_a_device_event_and_nothing_else():
    from paddlebox_tpu.obs.program_scopes import unscoped_map

    scopes = scope_map(TPU_TEXT)
    account = unscoped_map(TPU_TEXT, scopes)
    # no parameter, constant, tuple, get-tuple-element or bitcast, no loop of its
    # own, nothing inside a fused computation or a reduction's, nothing scoped;
    # the loop's condition is its body's as much as the body is
    assert sorted(account) == sorted(list(UNSCOPED_ON_THE_TPU) + ["compare.20"])
    assert account["compare.20"] == ["other", ""]
    assert not [n for n in account if scopes[n]]
    # the same text gives the same account, and the scopes are not its business
    assert unscoped_map(TPU_TEXT) == account and scope_map(TPU_TEXT) == scopes


@pytest.mark.parametrize("held,kind", [
    ([("dynamic-slice", "f32[1,8]"), ("bitcast", "f32[8]")], "stack"),
    ([("dynamic-update-slice", "bf16[4,8]"), ("convert", "bf16[1,8]"), ("copy", "bf16[1,8]")], "stack"),
    ([("dynamic-slice", "s32[1]"), ("add", "s32[]"), ("select", "s32[]"), ("compare", "pred[]")], "stack"),
    ([("dynamic-update-slice", "f32[4,8]"), ("add", "f32[1,8]")], "other"),
    # a kept residual named for the checkpoint policy is rounded in place on its way into the stack
    ([("dynamic-update-slice", "f32[4,8]"), ("reduce-precision", "f32[1,8]"), ("bitcast", "f32[1,1,8]")], "stack"),
    ([("convert", "bf16[8]")], "cast"),
    ([("convert", "s32[8]"), ("transpose", "s32[8]"), ("reshape", "s32[8]")], "cast"),
    ([("copy", "f32[8]")], "copy"),
    ([("transpose", "f32[8,4]"), ("copy", "f32[8,4]"), ("broadcast", "f32[8,4]")], "copy"),
    ([("copy-done", "f32[8]")], "copy"),
    ([("convert", "bf16[8]"), ("multiply", "bf16[8]")], "other"),
    ([("reduce-window", "s32[64,128]")], "other"),
    ([("broadcast", "f32[8]")], "other"),
    ([("slice", "f32[8]")], "other"),
])
def test_kind_of_reads_what_an_instruction_holds(held, kind):
    from paddlebox_tpu.obs.program_scopes import KINDS, kind_of

    assert kind_of(held) == kind and kind in KINDS


def _accounted(entry, text):
    """The entry's account against the text it was read from: every instruction
    without a scope that can be a device event of its own, each under one kind."""
    from paddlebox_tpu.obs.program_scopes import KINDS, _NO_EVENT, _executed, _parse

    ins, comps, main = _parse(text)
    own = [n for c in _executed(ins, comps, main) for n in comps[c]]
    expect = {n for n in own if not entry["scopes"][n] and ins[n].opcode not in _NO_EVENT}
    assert set(entry["unscoped"]) == expect and len(expect) > 10
    assert {kind for kind, _ in entry["unscoped"].values()} <= set(KINDS)
    found = set(entry["scopes"].values())
    assert {serves for _, serves in entry["unscoped"].values()} <= found | {""}
    return ins


def test_the_ctr_superstep_accounts_for_what_its_scopes_leave_out(trained, tmp_path):
    import json

    from paddlebox_tpu.obs.program_scopes import unscoped_map

    entry = REGISTRY.get(PROGRAM)
    # the scopes are what the same text gave before there was an account, digit for digit
    assert entry["scopes"] == scope_map(trained["text"])
    assert entry["unscoped"] == unscoped_map(trained["text"])
    ins = _accounted(entry, trained["text"])
    # the batch scan reads each batch's ids out of the pass's arrays: a stack's slice
    stacks = [n for n, (kind, _) in entry["unscoped"].items() if kind == "stack"]
    assert stacks and all(ins[n].opcode in ("fusion", "dynamic-slice", "dynamic-update-slice")
                          for n in stacks)
    # the executable's own figures, each only where the backend gives it: the CPU gives no peak
    memory = entry["memory"]
    assert memory["temp_bytes"] > 0 and memory["argument_bytes"] > 0
    assert set(memory) <= {"peak_bytes", "temp_bytes", "argument_bytes", "output_bytes", "alias_bytes"}
    assert all(isinstance(v, int) for v in memory.values())
    # the dump carries both; an entry of a build before them reads as having none
    old = ProgramRegistry()
    old._programs["superstep/train/old"] = {"fun_name": "superstep", "instructions": 1,
                                            "scopes": {"fusion.1": "pull/expand"}}
    old.dump(str(tmp_path / "old.json"))
    REGISTRY.dump(str(tmp_path / "scopes.json"))
    with open(tmp_path / "scopes.json") as f:
        doc = json.load(f)[PROGRAM]
    assert doc["unscoped"] == entry["unscoped"] and doc["memory"] == memory
    assert doc["scopes"] == entry["scopes"]
    with open(tmp_path / "old.json") as f:
        doc = json.load(f)["superstep/train/old"]
    assert doc.get("unscoped") is None and doc.get("memory") is None


def test_memory_of_gives_each_figure_only_where_the_backend_does():
    from types import SimpleNamespace

    from paddlebox_tpu.obs.program_scopes import memory_of

    tpu = SimpleNamespace(memory_analysis=lambda: SimpleNamespace(
        peak_memory_in_bytes=9_586_000_000, temp_size_in_bytes=8_916_000_000,
        argument_size_in_bytes=4, output_size_in_bytes=3, alias_size_in_bytes=2,
        generated_code_size_in_bytes=1))
    assert memory_of(tpu) == {"peak_bytes": 9_586_000_000, "temp_bytes": 8_916_000_000,
                              "argument_bytes": 4, "output_bytes": 3, "alias_bytes": 2}
    cpu = SimpleNamespace(memory_analysis=lambda: SimpleNamespace(temp_size_in_bytes=7))
    assert memory_of(cpu) == {"temp_bytes": 7}  # absent is absent, not 0

    def no_analysis():
        raise NotImplementedError

    assert memory_of(SimpleNamespace(memory_analysis=no_analysis)) == {}
    assert memory_of(SimpleNamespace(memory_analysis=lambda: None)) == {}
