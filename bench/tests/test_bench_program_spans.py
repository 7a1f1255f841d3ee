"""The reduction of the program's spans and scopes (``bench/program_spans``)
on hand-made traces whose times are known."""
from __future__ import annotations

import pytest

from bench import program_spans as ps
from bench import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def _host(name, s, e, line="python"):
    return (HOST, line, name, float(s), float(e))


def _save_and_steps():
    """One save (0-100 ns) of two leaves, then two steps; a pull outside
    the save, and a write on another thread during it."""
    return [
        _host("ckpt.save", 0, 100),
        _host("ckpt.pull", 0, 10), _host("ckpt.encode", 10, 20),
        _host("xufs.write", 20, 25), _host("xufs.close", 25, 45),
        _host("xufs.cache_store", 27, 33), _host("wal.append", 35, 43),
        _host("ckpt.pull", 45, 55), _host("ckpt.encode", 55, 60),
        _host("xufs.write", 60, 62), _host("xufs.close", 62, 90),
        _host("xufs.cache_store", 63, 70), _host("wal.append", 70, 88),
        _host("xufs.write", 30, 31, line="other"),
        _host("ckpt.pull", 150, 160),
        _host("pipeline.read", 100, 105),
        _host("pipeline.to_device", 105, 106),
        _host("train.dispatch", 106, 110),
        _host("pipeline.read", 200, 204),
        _host("pipeline.to_device", 204, 206),
        _host("train.dispatch", 206, 210),
    ]


def test_span_seconds_within_another_span():
    ev = _save_and_steps()
    assert ps.span_seconds(ev, 0, 300, "ckpt.pull") == pytest.approx(
        [10e-9, 10e-9, 10e-9])
    assert ps.span_seconds(ev, 0, 300, "ckpt.pull", within="ckpt.save") \
        == pytest.approx([10e-9, 10e-9])
    # the write on another thread is not nested in this thread's save
    assert ps.span_seconds(ev, 0, 300, "xufs.write", within="ckpt.save") \
        == pytest.approx([5e-9, 2e-9])
    # clipped to the window: spans that end after it are left out
    assert ps.span_seconds(ev, 0, 50, "ckpt.pull") == pytest.approx([10e-9])


def test_self_seconds_leave_out_the_children():
    ev = _save_and_steps()
    # 20 - (6 + 8) and 28 - (7 + 18)
    assert ps.self_seconds(ev, 0, 300, "xufs.close", within="ckpt.save") \
        == pytest.approx([6e-9, 3e-9])


def test_readings_of_a_save_and_two_steps():
    ev = _save_and_steps()
    r = ps.readings(ev, {}, 0.0, 300.0, ckpt_bytes=4000)
    assert r["ckpt_pull_s"] == pytest.approx(20e-9)
    assert r["ckpt_encode_s"] == pytest.approx(15e-9)
    assert r["ckpt_buffer_s"] == pytest.approx((5 + 2 + 6 + 3) * 1e-9)
    assert r["ckpt_cache_store_s"] == pytest.approx(13e-9)
    assert r["ckpt_wal_append_s"] == pytest.approx(26e-9)
    assert r["ckpt_pull_gb_per_s"] == pytest.approx(4000 / 20e-9 / 1e9)
    assert r["input_read_ms"] == pytest.approx(1e3 * 9e-9 / 2)
    assert r["input_h2d_ms"] == pytest.approx(1e3 * 3e-9 / 2)
    # no device plane: no scope
    assert all(r[f"{s}_device_ms"] is None for s in ps.SCOPES)


def test_readings_without_the_programs_spans_are_none():
    """A trace of a program that has no spans (the benchmark's own only)
    reads ``None``, never 0."""
    ev = [_host("window", 0, 300), _host("step", 0, 100),
          _host("ckpt_save", 10, 90), (DEV, OPS, "fusion.1", 5.0, 8.0),
          (DEV, MODS, "jit_train_step", 5.0, 8.0)]
    r = ps.readings(ev, {}, 0.0, 300.0, ckpt_bytes=0)
    assert set(r) >= {"ckpt_pull_s", "input_read_ms", "attention_device_ms"}
    assert all(v is None for v in r.values()), r


WHILE = ("%while.1 = (s32[], bf16[8,128]{1,0:T(8,128)(2,1)S(1)}) "
         "while((s32[], bf16[8,128]{1,0}) %tuple), condition=%c, body=%b")
F1 = ("%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} "
      "%p), kind=kOutput, calls=%fused_computation")
F2 = "%fusion.2 = f32[8]{0} fusion(bf16[8,128]{1,0} %q), kind=kLoop"
F3 = "%convolution.3 = bf16[8,8]{1,0} convolution(%a, %b), dim_labels=bf_io"
CP = "%copy.4 = bf16[8,128]{1,0} copy(bf16[8,128]{0,1} %r)"


def _scoped():
    """A ``while`` (0-100) whose body holds two attention ops that overlap
    (10-30, 20-40), one mlp op (50-60) and an unscoped copy (70-80)."""
    ev = [(DEV, OPS, WHILE, 0.0, 100.0), (DEV, OPS, F1, 10.0, 30.0),
          (DEV, OPS, F2, 20.0, 40.0), (DEV, OPS, F3, 50.0, 60.0),
          (DEV, OPS, CP, 70.0, 80.0), (DEV, MODS, "jit_train_step(7)", 0.0,
                                        100.0)]
    stacks = {WHILE: "jit(train_step)/attention/while",
              F1: "jit(train_step)/jvp(attention)/dot_general",
              F2: "jit(train_step)/transpose(jvp(attention))/mul",
              F3: "jit(train_step)/jvp()/while/body/mlp/dot_general"}
    return ev, stacks


def test_scope_seconds_counts_leaf_ops_once():
    ev, stacks = _scoped()
    # the while is not counted, its overlapping body ops once: 10-40
    assert ps.scope_seconds(ev, DEV, "attention", stacks) == pytest.approx(
        30e-9)
    assert ps.scope_seconds(ev, DEV, "mlp", stacks) == pytest.approx(10e-9)
    # the copy has no stack: under no scope
    assert ps.scope_seconds(ev, DEV, "head", stacks) == 0.0
    assert ps.scope_seconds(ev, DEV, "attention", stacks, 25.0, 100.0) \
        == pytest.approx(15e-9)


def test_scope_is_a_whole_path_segment():
    ev, stacks = _scoped()
    stacks = dict(stacks, **{CP: "jit(train_step)/attention_mask/copy"})
    assert ps.scope_seconds(ev, DEV, "attention", stacks) == pytest.approx(
        30e-9)


def test_readings_of_the_scopes_per_run_of_the_step():
    ev, stacks = _scoped()
    ev += [(DEV, OPS, F3, 150.0, 170.0),
           (DEV, MODS, "jit_train_step(7)", 140.0, 180.0)]
    r = ps.readings(ev, stacks, 0.0, 200.0)
    assert r["attention_device_ms"] == pytest.approx(1e3 * 30e-9 / 2)
    assert r["mlp_device_ms"] == pytest.approx(1e3 * 30e-9 / 2)
    assert r["head_device_ms"] is None and r["optimizer_device_ms"] is None


@pytest.mark.parametrize("name,want", [
    (WHILE, True), (F1, False), (F3, False), (CP, False),
    ("%call.5 = f32[] call(f32[] %x), to_apply=%f", True),
    ("%conditional.6 = f32[] conditional(pred[] %p, f32[] %a, f32[] %b)",
     True),
    ("%custom-call.7 = f32[8] custom-call(f32[8] %x), "
     "custom_call_target=\"tpu_custom_call\"", False),
    ("while.162", True), ("fusion.212", False),
])
def test_containers_are_told_by_their_opcode(name, want):
    assert ps.is_container(name) is want


def test_a_tiny_checkpoint_cell_yields_every_host_reading():
    """The checkpoint cell at a tiny size on the CPU, traced: every host
    span's reading is there (the CPU trace has no device plane, so the
    scopes read ``None``)."""
    from bench.tests import tiny

    workload = next(w for w in tiny.workloads("train")
                    if tiny.traffic(w).get("ckpt_every"))
    out = ps.measure(workload, tiny.SEED, 1.0, need_tpu=False,
                     spec=tiny.spec(), overrides=tiny.overrides(workload))
    r = out["readings"]
    assert out["ckpt_save_s"] and out["ckpt_bytes"] > 0
    for key in ("ckpt_pull_s", "ckpt_encode_s", "ckpt_buffer_s",
                "ckpt_cache_store_s", "ckpt_wal_append_s",
                "ckpt_pull_gb_per_s", "input_read_ms", "input_h2d_ms"):
        assert r[key] is not None and r[key] > 0, key
    parts = sum(r[k] for k in ("ckpt_pull_s", "ckpt_encode_s",
                               "ckpt_buffer_s", "ckpt_cache_store_s",
                               "ckpt_wal_append_s"))
    saves = out["ckpt_save_s"]
    assert parts <= sum(saves) / len(saves)
    assert all(r[f"{s}_device_ms"] is None for s in ps.SCOPES)


def test_name_stacks_from_the_op_metadata_of_an_xplane(tmp_path):
    """A TPU trace keeps an op's ``tf_op`` on its event metadata, beside
    other stats; an op without it has no stack."""
    from jax.profiler import ProfileData

    xspace = '''
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 7 offset_ps: 0 duration_ps: 5000 }
        events { metadata_id: 8 offset_ps: 6000 duration_ps: 2000 } }
      event_metadata { key: 7 value { id: 7 name: "%fusion.1 = f32[] fusion()"
        stats { metadata_id: 1 str_value: "jit(f)/jvp(attention)/dot:" }
        stats { metadata_id: 2 ref_value: 3 } } }
      event_metadata { key: 8 value { id: 8 name: "%copy.2 = f32[] copy()" } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
      stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
      stat_metadata { key: 3 value { id: 3 name: "convolution fusion" } } }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
      event_metadata { key: 1 value { id: 1 name: "ckpt.pull" } } }
    '''
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace))
    f1 = "%fusion.1 = f32[] fusion()"
    events, stacks = ps.load(str(path))
    assert stacks == {f1: "jit(f)/jvp(attention)/dot:"}
    assert (HOST, "python", "ckpt.pull", 0.0, 100.0) in events
    assert ps.scope_seconds(events, DEV, "attention", stacks) == \
        pytest.approx(5e-9)


def test_a_trace_recorded_on_a_tpu():
    """``data/trace_tpu_program.json``, recorded by
    ``record_program_trace.py`` on a TPU v5e: two runs of a program whose
    first matmul is under ``attention`` and whose second runs twice in a
    ``while`` under ``mlp``, each run's result pulled in ``ckpt.pull``."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_tpu_program.json")) as f:
        data = json.load(f)
    assert data["stack_stat"] == ps.STACK_STAT
    ev = [tuple(e) for e in data["events"]]
    stacks = data["stacks"]
    runs = trace.module_seconds(ev, DEV, "train_step")
    assert len(runs) == 2
    ops = trace.op_events(ev, DEV)
    whiles = [e for e in ops if ps.is_container(e[2])]
    assert len(whiles) == 2
    att = ps.scope_seconds(ev, DEV, "attention", stacks)
    mlp = ps.scope_seconds(ev, DEV, "mlp", stacks)
    # one 2048-cube bf16 matmul takes ~90 us; the mlp's run twice a call
    assert 2 * 80e-6 < att < 2 * 100e-6
    assert 4 * 80e-6 < mlp < 4 * 100e-6
    # the mlp ops are the while's body: counted once, and less than it
    assert mlp < sum((e - s) / 1e9 for *_, s, e in whiles)
    assert att + mlp < sum(runs)
    lo, hi = trace.window_bounds(ev, "window")
    assert len(ps.span_seconds(ev, lo, hi, "ckpt.pull")) == 2
    r = ps.readings(ev, stacks, lo, hi)
    assert r["attention_device_ms"] == pytest.approx(1e3 * att / 2)
    assert r["head_device_ms"] is None and r["ckpt_pull_s"] is None

