"""Native C++ slot parser: exact parity with the Python parser + speed.

The Python parser (data/parser.py) is the semantics oracle; the native tier
must agree record-for-record on every field, including logkey decoding,
zero dropping, unused-slot skipping, and skip-record rules.
"""

import time

import numpy as np
import pytest

from paddlebox_tpu.data import SlotInfo, SlotSchema
from paddlebox_tpu.data.parser import parse_line
from paddlebox_tpu.utils import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native parser lib unavailable"
)


def gen_lines(rng, n, with_logkey=False, n_sparse=5, zero_rate=0.1):
    lines = []
    for i in range(n):
        parts = []
        if with_logkey:
            sid = int(rng.integers(0, 1 << 32))
            logkey = "0" * 11 + f"{int(rng.integers(0, 4095)):03x}" + f"{int(rng.integers(0, 255)):02x}" + f"{sid:016x}"
            parts.append(f"1 {logkey}")
        parts.append(f"1 {rng.uniform(0, 1):.4f}")  # label float
        for s in range(n_sparse):
            cnt = int(rng.integers(1, 4))
            vals = [
                0 if rng.uniform() < zero_rate else int(rng.integers(1, 10**12))
                for _ in range(cnt)
            ]
            parts.append(f"{cnt} " + " ".join(map(str, vals)))
        lines.append(" ".join(parts))
    return lines


def schema_of(with_logkey, n_sparse=5, unused=()):
    slots = [SlotInfo("label", type="float", dense=True, dim=1)]
    for i in range(n_sparse):
        slots.append(SlotInfo(f"s{i}", used=i not in unused))
    return SlotSchema(slots, label_slot="label", parse_logkey=with_logkey)


def assert_records_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.u64_values, rb.u64_values)
        np.testing.assert_array_equal(ra.u64_offsets, rb.u64_offsets)
        np.testing.assert_allclose(ra.f_values, rb.f_values, rtol=1e-6)
        np.testing.assert_array_equal(ra.f_offsets, rb.f_offsets)
        assert ra.search_id == rb.search_id
        assert ra.cmatch == rb.cmatch and ra.rank == rb.rank
        assert ra.ins_id == rb.ins_id


@pytest.mark.parametrize("with_logkey", [False, True])
@pytest.mark.parametrize("unused", [(), (1, 3)])
def test_native_matches_python(with_logkey, unused):
    rng = np.random.default_rng(0)
    schema = schema_of(with_logkey, unused=unused)
    lines = gen_lines(rng, 200, with_logkey)
    want = [r for r in (parse_line(l, schema) for l in lines) if r is not None]
    buf = ("\n".join(lines) + "\n").encode()
    got = native.parse_buffer(buf, schema)
    assert_records_equal(got, want)


def test_native_skips_all_zero_records():
    schema = schema_of(False, n_sparse=2)
    buf = b"1 0.5 1 0 1 0\n1 0.5 1 7 1 8\n"
    stats = {}
    recs = native.parse_buffer(buf, schema, stats)
    assert len(recs) == 1 and stats["skipped"] == 1
    assert list(recs[0].slot_keys(0)) == [7]


def test_native_error_diagnostics():
    schema = schema_of(False, n_sparse=2)
    with pytest.raises(ValueError, match="line 2.*zero-count"):
        native.parse_buffer(b"1 1.0 1 5 1 6\n1 1.0 0 1 6\n", schema)
    with pytest.raises(ValueError, match="truncated"):
        native.parse_buffer(b"1 1.0 2 5\n", schema)


def test_native_dataset_path_and_speed(tmp_path):
    """Dataset uses the native path by default; native is faster."""
    from paddlebox_tpu import config
    from paddlebox_tpu.data import BoxPSDataset
    from paddlebox_tpu.table import HostSparseTable, SparseOptimizerConfig, ValueLayout

    rng = np.random.default_rng(1)
    schema = schema_of(False)
    lines = gen_lines(rng, 20000, False)
    p = tmp_path / "big.txt"
    p.write_text("\n".join(lines) + "\n")

    table = HostSparseTable(ValueLayout(embedx_dim=4), SparseOptimizerConfig(), n_shards=4)

    def load(native_on):
        config.set_flag("enable_native_parser", native_on)
        ds = BoxPSDataset(schema, table, batch_size=256, read_threads=1)
        ds.set_date("20260101")
        ds.set_filelist([str(p)])
        t0 = time.perf_counter()
        ds.load_into_memory()
        dt = time.perf_counter() - t0
        ds.begin_pass(round_to=64)
        recs = ds.records
        ds.end_pass(None, shrink=False)
        return recs, dt

    try:
        recs_n, dt_n = load(True)
        recs_p, dt_p = load(False)
    finally:
        config.set_flag("enable_native_parser", True)
    assert_records_equal(recs_n, recs_p)
    # native should beat the python line loop comfortably; allow jitter
    assert dt_n < dt_p, (dt_n, dt_p)
    print(f"native {dt_n * 1e3:.1f}ms vs python {dt_p * 1e3:.1f}ms "
          f"({dt_p / dt_n:.1f}x)")


def test_native_edge_parity():
    """Edge cases that must match the oracle exactly."""
    # |v| == 1e-6 is KEPT by the oracle (drops only abs(v) < 1e-6)
    schema = SlotSchema(
        [SlotInfo("f0", type="float"), SlotInfo("s0")], label_slot=None
    )
    buf = b"2 1e-6 1e-7 1 5\n"
    want = parse_line("2 1e-6 1e-7 1 5", schema)
    got = native.parse_buffer(buf, schema)
    assert_records_equal(got, [want])
    assert len(got[0].slot_floats(0)) == 1

    # short (17..31 char) logkeys decode like the oracle's slices
    schema_lk = schema_of(True, n_sparse=1)
    lk = "0" * 11 + "abc" + "1f" + "1234"  # 20 chars: search slice = '1234'
    line = f"1 {lk} 1 0.5 1 9"
    want = parse_line(line, schema_lk)
    got = native.parse_buffer((line + "\n").encode(), schema_lk)
    assert_records_equal(got, [want])
    assert got[0].search_id == 0x1234 and got[0].cmatch == 0xABC

    # NaN floats are KEPT (oracle's abs(v) < 1e-6 is False for NaN); the
    # downstream NaN guardrails own rejection, not the parser
    schema_nan = SlotSchema(
        [SlotInfo("f0", type="float"), SlotInfo("s0")], label_slot=None
    )
    want = parse_line("2 nan 0.5 1 5", schema_nan)
    got = native.parse_buffer(b"2 nan 0.5 1 5\n", schema_nan)
    assert len(want.f_values) == 2 and np.isnan(want.f_values[0])
    assert len(got[0].f_values) == 2 and np.isnan(got[0].f_values[0])
    np.testing.assert_array_equal(got[0].f_offsets, want.f_offsets)

    # non-hex chars in the logkey reject the parse (oracle: int(_,16) raises)
    schema_lk1 = schema_of(True, n_sparse=1)
    bad = "0" * 11 + "xyz" + "1f" + "1234"
    with pytest.raises(ValueError, match="hex"):
        native.parse_buffer(f"1 {bad} 1 0.5 1 9\n".encode(), schema_lk1)
    with pytest.raises(ValueError):
        parse_line(f"1 {bad} 1 0.5 1 9", schema_lk1)

    # ins_id + logkey: the logkey wins as ins_id (parser.py overwrite)
    slots = [SlotInfo("label", type="float", dense=True, dim=1), SlotInfo("s0")]
    schema_both = SlotSchema(slots, label_slot="label",
                             parse_ins_id=True, parse_logkey=True)
    lk32 = "0" * 11 + "001" + "02" + f"{77:016x}"
    line = f"1 myid 1 {lk32} 1 1.0 1 3"
    want = parse_line(line, schema_both)
    got = native.parse_buffer((line + "\n").encode(), schema_both)
    assert_records_equal(got, [want])
    assert got[0].ins_id == lk32


def test_native_parses_a_record_of_4096_keys_in_one_slot(tmp_path):
    """A token record: one 65 KB line holding a dense slot of 4,096 floats and
    a sparse slot of 4,096 thirteen-digit keys. No line or per-slot limit:
    the columnar store, the float matrix and the resident pass's offsets
    (past the uint8 count form, which holds 255 keys a slot) carry it."""
    from paddlebox_tpu.train.resident_step import ResidentPass

    T, n = 4096, 6
    rng = np.random.default_rng(28)
    ids = rng.integers(0, 19360, (n, T))
    lines = [f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
             + " ".join(str(10**12 + i) for i in row) for row in ids.tolist()]
    assert min(len(ln) for ln in lines) > 65_000
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=T), SlotInfo("tokens")],
        label_slot="label")
    buf = ("\n".join(lines) + "\n").encode()
    recs = native.parse_buffer(buf, schema)
    assert len(recs) == n
    assert_records_equal(recs, [parse_line(ln, schema) for ln in lines])
    store = native.parse_buffer_columnar(buf, schema)
    assert len(store) == n and np.all(store.key_counts() == T)
    assert np.array_equal(np.asarray(store.u64_values).reshape(n, T), ids + 10**12)
    assert np.array_equal(store.float_slot_matrix(schema.float_slot_index("ids"), T), ids)

    class Ws:  # the rows a working set would resolve: here the id itself
        n_mesh_shards, capacity = 1, 32768

        @staticmethod
        def lookup(keys):
            return (np.asarray(keys) - 10**12).astype(np.int32)

    rp = ResidentPass(store, Ws(), schema, dense_slot="ids", dense_dim=T)
    assert rp.counts is None and rp.off.shape == (n, 2)  # 4,096 keys: the offset matrix
    assert np.array_equal(np.asarray(rp.off)[:, 1] - np.asarray(rp.off)[:, 0], np.full(n, T))
    assert np.array_equal(np.asarray(rp.dense), ids)
