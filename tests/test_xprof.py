"""obs/xprof.py: from a profiler trace to numbers.

Three kinds of trace: a real one taken here on the CPU backend (events
carry `hlo_op`/`hlo_module` and no scope, so the tests hand `summarize`
a map taken from the executable's text: `_scopes_from_hlo`), a
synthetic one in the TPU's format written from a text proto (scopes,
FLOPs and bytes come from the file's event metadata, read off the wire
format), and a small real one recorded on a v5e
(`tests/data/xprof/v5e_probe.xplane.pb`, PR 24's probe)."""

from __future__ import annotations

import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from hyperion_tpu.obs import xprof
from hyperion_tpu.utils import profiling

V5E_PROBE = Path(__file__).parent / "data" / "xprof" / "v5e_probe.xplane.pb"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def _scopes_from_hlo(text: str) -> dict[str, str]:
    """{instruction name: op_name} of one executable's text
    (`jitted.lower(...).compile().as_text()`): what a TPU trace carries
    in its event metadata and a CPU trace does not."""
    return {m.group(1): m.group(2)
            for m in map(_INSTR.match, text.splitlines()) if m}


# ------------------------------------------------------------- scope_of

@pytest.mark.parametrize("op_name, want", [
    ("jit(_tick_impl)/Llama/layer_3/attn/kv_read/gather",
     ("", "Llama/layer_*/attn/kv_read")),
    ("jit(_tick_impl)/Llama/layer_11/attn/kv_read/gather",
     ("", "Llama/layer_*/attn/kv_read")),
    # a trailing colon: the TPU's `tf_op` stat is `<op_name>:<type>`
    ("jit(f)/alpha/dot_general:", ("", "alpha")),
    # call wrappers go, whatever they wrap
    ("jit(_tick_impl)/sampling/jit(take_along_axis)/gather",
     ("", "sampling")),
    ("jit(_tick_impl)/sampling/vmap(jit(_gumbel))/jit(_uniform)/add",
     ("", "sampling")),
    # a wrapper in the last place leaves no primitive to strip
    ("jit(_tick_impl)/sampling/jit(take_along_axis)", ("", "sampling")),
    # transforms keep the module they wrap; transpose marks backward
    ("jit(train_step)/jvp(TransformerLM)/block_0/attn/q_proj/dot_general",
     ("", "TransformerLM/block_*/attn/q_proj")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/block_7/mlp/mul",
     ("bwd", "TransformerLM/block_*/mlp")),
    ("jit(f)/transpose(jvp())/dot_general:", ("bwd", "unscoped")),
    # flax names the module, the code names the scope: once is enough
    ("jit(_tick_impl)/Llama/lm_head/lm_head/dot_general",
     ("", "Llama/lm_head")),
    ("pjit(step)/optimizer/grad_clip/mul", ("", "optimizer/grad_clip")),
    # a scan's body, a closed call and an einsum's spec are no places
    ("jit(train_step)/while/body/closed_call/grad_accum/add",
     ("", "grad_accum")),
    ("jit(train_step)/while/body/closed_call/jvp(TransformerLM)/block_0/"
     "attn/attention/bhqk,bkhd->bqhd/dot_general",
     ("", "TransformerLM/block_*/attn/attention")),
    ("jit(train_step)/while", ("", "unscoped")),
    # nothing below a program's root
    ("jit(_tick_impl)/add", ("", "unscoped")),
    ("reduce_sum", ("", "unscoped")),
    ("st['lengths']", ("", "unscoped")),
    ("", ("", "unscoped")),
    (None, ("", "unscoped")),
])
def test_scope_of(op_name, want):
    assert xprof.scope_of(op_name) == want


@pytest.mark.parametrize("named, want", [
    ({"a": "jit(f)/transpose(jvp(M))/layer_2/mlp/fc1/dot_general",
      "b": "jit(f)/transpose(jvp(M))/layer_2/mlp/fc2/dot_general"},
     ("bwd", "M/layer_*/mlp")),
    ({"a": "jit(f)/jvp(M)/loss/exp", "b": "jit(f)/transpose(jvp(M))/loss/mul"},
     ("", "M/loss")),
    ({"a": "jit(f)/sampling/sort", "b": "jit(f)/slot_state/add"}, None),
    ({"a": "jit(f)/sampling/sort", "b": "reduce_sum"}, ("", "sampling")),
    ({}, None),
])
def test_from_neighbours(named, want):
    line = ("%fusion.9 = f32[4]{0:T(1024)} fusion(s32[4]{0} %a, f32[4]{0} "
            "%b), kind=kCustom, calls=%fused_computation.3")

    def reads(ln):
        return [(named.get(i), None) for i in xprof._operands(ln)]

    assert xprof._operands(line) == ["a", "b"]
    assert xprof._from_neighbours(line, reads) == want
    # what reads it is asked only where nothing it reads has a scope
    readers = [("jit(f)/optimizer/mul", None)]
    assert xprof._from_neighbours(line, reads, lambda ln: readers) == (
        want if named else ("", "optimizer"))


def test_scopes_from_hlo():
    text = '''
HloModule jit_f, is_scheduled=true

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  ROOT %t = f32[4] tanh(%p), metadata={op_name="jit(f)/alpha/tanh" stack_frame_id=3}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0), metadata={op_name="x"}
  %wrapped_tanh = f32[4] fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/alpha/tanh" stack_frame_id=3}
  ROOT %sort.0 = f32[4] sort(%wrapped_tanh), dimensions={0}, metadata={op_name="jit(f)/beta/jit(sort)/sort"}
}
'''
    m = _scopes_from_hlo(text)
    assert m["wrapped_tanh"] == "jit(f)/alpha/tanh"
    assert m["sort.0"] == "jit(f)/beta/jit(sort)/sort"
    assert m["x"] == "x" and "p" not in m


# --------------------------------------------- a real trace, CPU backend

@jax.jit
def _two_scopes(x, w):
    with jax.named_scope("alpha"):
        y = jnp.tanh(x @ w)
    with jax.named_scope("beta"):
        z = jnp.sort(y, axis=-1)
    return z.sum()


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("cpu_trace")
    x = jnp.ones((256, 256))
    _two_scopes(x, x).block_until_ready()       # compiled before the trace
    with profiling.capture(d):
        with profiling.annotate("serve.step", tick=7):
            _two_scopes(x, x).block_until_ready()
            with profiling.annotate("serve.step/admit", bucket=64):
                time.sleep(0.03)                # the device idles in here
            _two_scopes(x, x).block_until_ready()
    scopes = {"jit__two_scopes": _scopes_from_hlo(
        _two_scopes.lower(x, x).compile().as_text())}
    return d, xprof.summarize(d, scopes=scopes)


class TestCpuTrace:
    def test_both_scopes_have_seconds(self, cpu_trace):
        _, s = cpu_trace
        rows = {r["scope"]: r for r in s["device_rows"]}
        assert rows["alpha"]["s"] > 0 and rows["beta"]["s"] > 0
        assert all(r["program"] == "jit__two_scopes"
                   for r in s["device_rows"])
        # the scopes came from the map: the CPU's events carry none
        assert s["scope_source_s"]["given"] > 0
        assert s["scope_source_s"]["event_stat"] == 0

    def test_unscoped_is_always_reported(self, cpu_trace):
        _, s = cpu_trace
        assert "unscoped_s" in s and 0 <= s["unscoped_share"] < 0.5
        assert "unscoped" in xprof.to_markdown(s)

    def test_without_the_map_everything_is_unscoped(self, cpu_trace):
        d, _ = cpu_trace
        s = xprof.summarize(d)
        assert s["unscoped_share"] == pytest.approx(1.0)
        assert [r["scope"] for r in s["device_rows"]] == ["unscoped"]

    def test_rows_sum_to_busy_and_idle_to_the_window(self, cpu_trace):
        _, s = cpu_trace
        assert sum(r["s"] for r in s["device_rows"]) == pytest.approx(
            s["busy_s"], rel=0.02)
        assert s["busy_s"] + s["idle_s"] == pytest.approx(s["window_s"])
        assert sum(r["s"] for r in s["idle_by_span"]) == pytest.approx(
            s["idle_s"], rel=1e-6)

    def test_inner_span_names_the_gap(self, cpu_trace):
        _, s = cpu_trace
        gap = s["longest_gaps"][0]
        assert gap["s"] >= 0.025
        assert gap["span"] == "serve.step/admit"     # not `serve.step`
        assert gap["before"] == gap["after"] == "jit__two_scopes"
        top = s["idle_by_span"][0]
        assert top["span"] == "serve.step/admit" and top["share"] > 0.8

    def test_annotation_arguments_survive(self, cpu_trace):
        _, s = cpu_trace
        # its own argument and the enclosing step's
        assert s["longest_gaps"][0]["args"] == {"tick": 7, "bucket": 64}

    def test_host_spans_and_module_runs(self, cpu_trace):
        _, s = cpu_trace
        spans = {r["span"]: r for r in s["host_spans"]}
        assert spans["serve.step"]["n"] == 1
        assert spans["serve.step/admit"]["max_s"] >= 0.03
        assert spans["serve.step"]["total_s"] \
            > spans["serve.step/admit"]["total_s"]
        (mod,) = s["modules"]
        assert mod["program"] == "jit__two_scopes" and mod["n"] == 2

    def test_no_flops_no_columns(self, cpu_trace):
        _, s = cpu_trace
        assert all("flops" not in r and "roofline_pct" not in r
                   for r in s["device_rows"])
        assert "GFLOP" not in xprof.to_markdown(s)

    def test_window_in_the_traces_seconds(self, cpu_trace):
        d, s = cpu_trace
        gap = s["longest_gaps"][0]          # the sleep inside `admit`
        inner = xprof.summarize(
            d, window=(gap["start_s"], gap["start_s"] + gap["s"]))
        assert inner["window_s"] == pytest.approx(gap["s"])
        assert inner["busy_s"] == pytest.approx(0.0, abs=1e-9)
        assert inner["idle_by_span"][0]["span"] == "serve.step/admit"
        # what is left is the step's own: after the program's end and
        # before `admit` opens
        assert inner["idle_by_span"][0]["share"] > 0.9

    def test_another_threads_spans_name_no_gap(self, cpu_trace, tmp_path):
        """A reader thread's `serve.step/sink` (tickprof `record=False`)
        overlaps the loop's spans without nesting in them: it is counted
        as a host span and leaves the idle split alone."""
        import threading

        x = jnp.ones((256, 256))
        go, stop = threading.Event(), threading.Event()

        def reader():
            go.wait()
            with profiling.annotate("serve.step/sink"):
                stop.wait()

        th = threading.Thread(target=reader)
        with profiling.capture(tmp_path):
            th.start()
            with profiling.annotate("serve.step", tick=1):
                _two_scopes(x, x).block_until_ready()
                go.set()
                with profiling.annotate("serve.step/admit"):
                    time.sleep(0.03)
                _two_scopes(x, x).block_until_ready()
            time.sleep(0.01)        # the sink outlives the step
            stop.set()
            th.join()
        s = xprof.summarize(tmp_path)
        by = {r["span"]: r["s"] for r in s["idle_by_span"]}
        assert "serve.step/sink" not in by
        assert by["serve.step/admit"] >= 0.025
        assert s["longest_gaps"][0]["span"] == "serve.step/admit"
        assert s["longest_gaps"][0]["args"] == {"tick": 1}
        assert "serve.step/sink" in {r["span"] for r in s["host_spans"]}

    def test_cli_summarizes_a_trace_directory(self, cpu_trace, capsys):
        from hyperion_tpu.obs.export import profile_main

        d, s = cpu_trace
        assert profile_main([str(d), "--summarize"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["busy_s"] == pytest.approx(s["busy_s"])
        assert profile_main([str(d), "--summarize", "--markdown"]) == 0
        assert "| program | pass | scope |" in capsys.readouterr().out

    def test_cli_without_a_trace_or_a_process(self, tmp_path, capsys):
        from hyperion_tpu.obs.export import profile_main

        assert profile_main([str(tmp_path), "--summarize"]) == 1
        assert "no live process" in capsys.readouterr().err


# ----------------------------------- a synthetic trace in the TPU's format

def _op(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us}000000 "
            f"duration_ps: {dur_us}000000 }}\n")


def _meta(mid, instr, op_name=None, flops=None, nbytes=None,
          operands=("p",)):
    stats = ""
    if op_name is not None:
        stats += f'stats {{ metadata_id: 1 str_value: "{op_name}:" }} '
    if flops is not None:
        stats += f"stats {{ metadata_id: 2 uint64_value: {flops} }} "
    if nbytes is not None:
        stats += f"stats {{ metadata_id: 3 uint64_value: {nbytes} }} "
    args = ", ".join(f"f32[8]{{0:T(8)}} %{o}" for o in operands)
    return (f'event_metadata {{ key: {mid} value {{ id: {mid} name: '
            f'"%{instr} = f32[8]{{0}} fusion({args}), kind=kCustom, '
            f'calls=%fused_computation.{mid}" {stats}}} }}\n')


STAT_NAMES = '''
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
  stat_metadata { key: 3 value { id: 3 name: "bytes_accessed" } }
'''


def _device(n, ops, metas, module="jit_train_step(99)", module_us=(0, 1000)):
    return (
        f'planes {{ name: "/device:TPU:{n}"\n'
        f'  lines {{ name: "XLA Modules" timestamp_ns: 0\n'
        f'    events {{ metadata_id: 100 offset_ps: {module_us[0]}000000 '
        f'duration_ps: {module_us[1]}000000 }} }}\n'
        f'  lines {{ name: "XLA Ops" timestamp_ns: 0\n{"".join(ops)} }}\n'
        f'{"".join(metas)}'
        f'  event_metadata {{ key: 100 value {{ id: 100 name: "{module}" }} }}\n'
        f'{STAT_NAMES} }}\n')


HOST = '''planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000
             stats { metadata_id: 1 int64_value: 3 } }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "train" } }
  event_metadata { key: 2 value { id: 2 name: "train.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
  stat_metadata { key: 1 value { id: 1 name: "step_num" } }
}
'''


def _write(tmp_path, text, name="synthetic"):
    from jax.profiler import ProfileData

    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture()
def two_devices(tmp_path):
    """Microseconds. Device 0: [0,400) layer_3 kv_read, [300,500) the
    same of layer_11 overlapping it, [600,900) a backward matmul. Device
    1: [100,200) with no op_name at all, [800,1000) the optimizer."""
    fwd = "jit(train_step)/jvp(Model)/layer_{}/attn/kv_read/gather"
    bwd = "jit(train_step)/transpose(jvp(Model))/layer_0/mlp/dot_general"
    d0 = _device(0, [_op(1, 0, 400), _op(2, 300, 200), _op(3, 600, 300)],
                 [_meta(1, "fusion.1", fwd.format(3)),
                  _meta(2, "fusion.2", fwd.format(11)),
                  _meta(3, "fusion.3", bwd)])
    d1 = _device(1, [_op(1, 100, 100), _op(2, 800, 200)],
                 [_meta(1, "copy.1"),
                  _meta(2, "fusion.9", "jit(train_step)/optimizer/mul")])
    return _write(tmp_path, d0 + d1 + HOST)


class TestSyntheticPlanes:
    def test_busy_is_the_union_and_idle_fills_the_window(self, two_devices):
        s = xprof.summarize(two_devices)
        assert s["devices"] == 2
        assert s["window_s"] == pytest.approx(1000e-6)
        per = {d["device"]: d for d in s["per_device"]}
        # [0,500) + [600,900), not 400 + 200 + 300
        assert per["/device:TPU:0"]["busy_s"] == pytest.approx(800e-6)
        assert per["/device:TPU:1"]["busy_s"] == pytest.approx(300e-6)
        assert s["busy_s"] == pytest.approx(550e-6)     # mean of devices
        assert s["busy_s"] + s["idle_s"] == pytest.approx(s["window_s"])
        for d in s["per_device"]:
            assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"])
        assert sum(r["s"] for r in s["idle_by_span"]) == pytest.approx(
            s["idle_s"])

    def test_layers_fold_into_one_row(self, two_devices):
        s = xprof.summarize(two_devices)
        rows = {(r["pass"], r["scope"]): r for r in s["device_rows"]}
        kv = rows[("", "Model/layer_*/attn/kv_read")]
        assert kv["ops"] == 2 and kv["program"] == "jit_train_step"
        assert kv["s"] == pytest.approx((400e-6 + 200e-6) / 2)

    def test_transposed_gradient_lands_under_bwd(self, two_devices):
        s = xprof.summarize(two_devices)
        rows = {(r["pass"], r["scope"]): r for r in s["device_rows"]}
        assert rows[("bwd", "Model/layer_*/mlp")]["s"] == pytest.approx(
            300e-6 / 2)
        assert ("", "Model/layer_*/mlp") not in rows

    def test_operation_without_op_name_is_unscoped(self, two_devices):
        s = xprof.summarize(two_devices)
        assert s["unscoped_s"] == pytest.approx(100e-6 / 2)
        assert s["scope_source_s"]["none"] == pytest.approx(100e-6 / 2)

    def test_absent_flops_leave_the_column_out(self, two_devices):
        s = xprof.summarize(two_devices, peaks={"flops_per_s": 1e12,
                                                "bytes_per_s": 1e11})
        assert all("flops" not in r and "bytes" not in r
                   and "roofline_pct" not in r for r in s["device_rows"])

    def test_idle_goes_to_the_innermost_program_span(self, two_devices):
        s = xprof.summarize(two_devices)
        by = {r["span"]: r["s"] for r in s["idle_by_span"]}
        # `train.fetch` covers [400,500): device 1 idles through it all,
        # device 0 not at all. The runtime's own span names nothing.
        assert by["train.fetch"] == pytest.approx(100e-6 / 2)
        assert "PjitFunction(step)" not in by
        assert by["train"] == pytest.approx(s["idle_s"] - 50e-6)
        gap = next(g for g in s["longest_gaps"]
                   if g["device"] == "/device:TPU:1")
        assert gap["s"] == pytest.approx(600e-6)
        assert gap["span"] == "train" and gap["args"] == {"step_num": 3}
        assert gap["before"] == gap["after"] == "jit_train_step"

    def test_flops_and_bytes_give_a_roofline_share(self, tmp_path):
        mm = "jit(train_step)/jvp(Model)/layer_0/mlp/fc1/dot_general"
        dev = _device(0, [_op(1, 0, 500), _op(2, 500, 500)],
                      [_meta(1, "fusion.1", mm, flops=400_000_000,
                             nbytes=1000),
                       _meta(2, "fusion.2",
                             "jit(train_step)/optimizer/add",
                             flops=10, nbytes=25_000_000)])
        path = _write(tmp_path, dev)
        s = xprof.summarize(path, peaks={"flops_per_s": 1e12,
                                         "bytes_per_s": 1e11})
        rows = {r["scope"]: r for r in s["device_rows"]}
        fc1 = rows["Model/layer_*/mlp/fc1"]
        # 4e8 FLOPs at 1e12/s = 400 us of the 500 it took
        assert fc1["flops"] == 400_000_000 and fc1["bound"] == "flops"
        assert fc1["roofline_pct"] == pytest.approx(80.0)
        opt = rows["optimizer"]
        # 25e6 bytes at 1e11/s = 250 us of 500
        assert opt["bound"] == "bytes"
        assert opt["roofline_pct"] == pytest.approx(50.0)
        assert "% of roofline" in xprof.to_markdown(s)
        # no peaks given, none stated by the plane: costs, no share
        bare = xprof.summarize(path)
        assert bare["peaks"] is None
        assert all("flops" in r and "roofline_pct" not in r
                   for r in bare["device_rows"])

    def test_a_while_keeps_only_the_seconds_its_body_leaves(self, tmp_path):
        body = "jit(train_step)/grad_accum/add"
        dev = _device(0, [_op(1, 0, 1000), _op(2, 100, 300),
                          _op(2, 500, 300)],
                      [_meta(1, "while.1", "jit(train_step)/while",
                             flops=999),
                       _meta(2, "fusion.2", body, flops=7)])
        s = xprof.summarize(_write(tmp_path, dev))
        rows = {r["scope"]: r for r in s["device_rows"]}
        assert rows["grad_accum"]["s"] == pytest.approx(600e-6)
        assert rows["unscoped"]["s"] == pytest.approx(400e-6)
        assert s["busy_s"] == pytest.approx(1000e-6)
        # costs are counted where the work is, not again on the loop
        assert rows["grad_accum"]["flops"] == 14
        assert "flops" not in rows["unscoped"]

    def test_a_given_map_fills_in_where_the_stat_is_missing(self, tmp_path):
        dev = _device(0, [_op(1, 0, 500)], [_meta(1, "fusion.1")])
        path = _write(tmp_path, dev)
        assert xprof.summarize(path)["unscoped_share"] == 1.0
        s = xprof.summarize(path, scopes={
            "jit_train_step": {"fusion.1": "jit(train_step)/loss/exp"}})
        assert s["device_rows"][0]["scope"] == "loss"
        assert s["scope_source_s"]["given"] == pytest.approx(500e-6)

    def test_a_compiler_made_operation_takes_its_operands_scope(
            self, tmp_path):
        attn = "jit(_tick_impl)/Llama/layer_3/attn/"
        dev = _device(0, [_op(n, 100 * n, 100) for n in range(1, 10)], [
            _meta(1, "fusion.1", attn + "kv_read/gather"),
            _meta(2, "fusion.2", attn + "attention/dot_general"),
            # no op_name of their own; 3 reads 1, 4 reads 3 and 1
            _meta(3, "reshape.3", operands=("fusion.1",)),
            _meta(4, "fusion.4", operands=("reshape.3", "fusion.1",
                                           "constant.9")),
            # reads two scopes: what they share
            _meta(5, "copy.5", operands=("fusion.1", "fusion.2")),
            # reads nothing that has a name and nothing reads it: stays
            # unscoped, and is listed
            _meta(6, "copy.6", operands=("param.0",)),
            # what it calls is not what it reads
            _meta(7, "fusion.7", operands=()),
            # a weight's prefetch reads a parameter: the scope of what
            # reads IT, through the unnamed `done` between them
            _meta(8, "slice-start.8", operands=("param.1",)),
            _meta(9, "slice-done.9", operands=("slice-start.8",)),
            _meta(10, "fusion.10", "jit(_tick_impl)/Llama/lm_head/dot_general",
                  operands=("slice-done.9",))],
            module="jit__tick_impl(7)")
        s = xprof.summarize(_write(tmp_path, dev))
        rows = {r["scope"]: r["s"] for r in s["device_rows"]}
        assert rows["Llama/layer_*/attn/kv_read"] == pytest.approx(300e-6)
        assert rows["Llama/layer_*/attn/attention"] == pytest.approx(100e-6)
        assert rows["Llama/layer_*/attn"] == pytest.approx(100e-6)
        assert rows["unscoped"] == pytest.approx(200e-6)
        assert rows["Llama/lm_head"] == pytest.approx(200e-6)
        assert s["scope_source_s"]["neighbours"] == pytest.approx(500e-6)
        assert s["scope_source_s"]["none"] == pytest.approx(200e-6)
        assert sorted((u["program"], u["op"].split(" = ")[0], u["n"])
                      for u in s["unscoped_ops"]) == [
            ("jit__tick_impl", "%copy.6", 1),
            ("jit__tick_impl", "%fusion.7", 1)]

    def test_launch_and_fetch_lag_and_the_runtime_under_the_fetch(
            self, tmp_path):
        """Two steps, microseconds. Step 0: dispatch [100,150), fetch
        [150,900) with `tokens` [160,700) and `finished` [700,890); the
        tick program runs [200,500). Step 1 (from 1000): dispatch
        [1100,1150), fetch [1150,1800), `tokens` [1150,1600), `finished`
        [1600,1800); the program runs [1160,1500). An upload's one-
        microsecond program at 120 is not the pair's. A runtime thread's
        `TransferFromDevice` [600,880) lies under step 0's fetch; the
        loop line's own `PjitFunction` names nothing."""
        names = ["serve.step", "serve.step/device",
                 "serve.step/device/dispatch", "serve.step/device/fetch",
                 "serve.step/device/fetch/tokens",
                 "serve.step/device/fetch/finished",
                 "PjitFunction(_tick_impl)", "TransferFromDevice",
                 "ThreadPool::Wait"]
        mid = {n: i + 1 for i, n in enumerate(names)}

        def ev(name, start_us, end_us):
            return (f"events {{ metadata_id: {mid[name]} offset_ps: "
                    f"{start_us}000000 duration_ps: "
                    f"{end_us - start_us}000000 }}\n")

        loop = "".join(ev(n, a + at, b + at) for at, rows in (
            (0, [("serve.step", 0, 1000), ("serve.step/device", 100, 900),
                 ("serve.step/device/dispatch", 100, 150),
                 ("PjitFunction(_tick_impl)", 100, 150),
                 ("serve.step/device/fetch", 150, 900),
                 ("serve.step/device/fetch/tokens", 160, 700),
                 ("serve.step/device/fetch/finished", 700, 890)]),
            (1000, [("serve.step", 0, 1000), ("serve.step/device", 100, 800),
                    ("serve.step/device/dispatch", 100, 150),
                    ("serve.step/device/fetch", 150, 800),
                    ("serve.step/device/fetch/tokens", 150, 600),
                    ("serve.step/device/fetch/finished", 600, 800)]))
            for n, a, b in rows)
        host = (
            'planes { name: "/host:CPU"\n'
            f'  lines {{ name: "python" timestamp_ns: 0\n{loop} }}\n'
            '  lines { name: "tpu-runtime/7" timestamp_ns: 0\n'
            f'{ev("TransferFromDevice", 600, 880)}'
            f'{ev("ThreadPool::Wait", 0, 2000)} }}\n'
            + "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                      f'name: "{n}" }} }}\n' for n, i in mid.items())
            + '}\n')
        dev = (
            'planes { name: "/device:TPU:0"\n'
            '  lines { name: "XLA Modules" timestamp_ns: 0\n'
            '    events { metadata_id: 101 offset_ps: 120000000 '
            'duration_ps: 1000000 }\n'
            '    events { metadata_id: 100 offset_ps: 200000000 '
            'duration_ps: 300000000 }\n'
            '    events { metadata_id: 100 offset_ps: 1160000000 '
            'duration_ps: 340000000 } }\n'
            '  lines { name: "XLA Ops" timestamp_ns: 0\n'
            f'{_op(1, 200, 300)}{_op(1, 1160, 340)} }}\n'
            f'{_meta(1, "fusion.1", "jit(_tick_impl)/Llama/lm_head/dot")}'
            '  event_metadata { key: 100 value { id: 100 name: '
            '"jit__tick_impl(7)" } }\n'
            '  event_metadata { key: 101 value { id: 101 name: '
            f'"jit_convert_element_type(3)" }} }}\n{STAT_NAMES} }}\n')
        s = xprof.summarize(_write(tmp_path, dev + host),
                            window=(0.0, 2000e-6))
        (lag,) = s["program_lag"]
        assert (lag["program"], lag["dispatch"], lag["n"]) == (
            "jit__tick_impl", "serve.step/device/dispatch", 2)
        # the run's start after the dispatch span's: 100 and 60 us
        assert lag["launch_median_s"] == pytest.approx(80e-6)
        assert lag["launch_total_s"] == pytest.approx(160e-6)
        # the fetch span's end after the run's: 400 and 300 us
        assert lag["fetch_median_s"] == pytest.approx(350e-6)
        assert lag["fetch_total_s"] == pytest.approx(700e-6)
        by = {r["span"]: r for r in s["idle_by_span"]}
        # idle under each array's wait: before the program and after it
        assert by["serve.step/device/fetch/tokens"]["s"] == pytest.approx(
            (40 + 200 + 10 + 100) * 1e-6)
        assert by["serve.step/device/fetch/finished"]["s"] == pytest.approx(
            (190 + 200) * 1e-6)
        assert sum(r["s"] for r in s["idle_by_span"]) == pytest.approx(
            s["idle_s"])
        under = {m["event"]: m for m in
                 by["serve.step/device/fetch/finished"]["meanwhile"]}
        # the runtime's transfer [600,880) under `finished` [700,890)
        assert under["TransferFromDevice"]["s"] == pytest.approx(180e-6)
        assert under["TransferFromDevice"]["thread"] == "tpu-runtime/7"
        assert under["ThreadPool::Wait"]["s"] == pytest.approx(390e-6)
        tokens = {m["event"]: m["s"] for m in
                  by["serve.step/device/fetch/tokens"]["meanwhile"]}
        assert tokens["TransferFromDevice"] == pytest.approx(100e-6)
        # the loop's own line is not another thread, and a span is no
        # runtime event
        assert not any(m["event"].startswith(("PjitFunction", "serve."))
                       for r in s["idle_by_span"]
                       for m in r.get("meanwhile", []))
        # only the spans with the most idle seconds carry the list
        assert sum("meanwhile" in r for r in s["idle_by_span"]) == min(
            xprof.MEANWHILE_SPANS, len(s["idle_by_span"]))
        text = xprof.to_markdown(s)
        assert "launch median ms" in text and "TransferFromDevice" in text

    def test_a_trace_with_no_device_work_is_refused(self, tmp_path):
        with pytest.raises(ValueError, match="no operation"):
            xprof.summarize(_write(tmp_path, HOST))
        with pytest.raises(FileNotFoundError):
            xprof.summarize(tmp_path / "nothing_here")


# ------------------------------------------- a real trace from the v5e

class TestV5eProbe:
    """`_scratch/probe_trace.py` of PR 24 on one TPU v5 lite: scopes
    `alpha` (matmul + tanh) and `beta` (sort), a gradient, three steps
    under `StepTraceAnnotation("train")`."""

    def test_scopes_flops_and_peaks_come_from_the_file(self):
        s = xprof.summarize(V5E_PROBE)
        assert s["devices"] == 1 and s["peaks"]["source"] == "trace"
        assert s["peaks"]["flops_per_s"] == pytest.approx(202.7e12)
        rows = {(r["pass"], r["scope"]): r for r in s["device_rows"]}
        assert rows[("", "alpha")]["flops"] > 2e8       # 2 x 512^3
        assert rows[("", "beta")]["s"] > rows[("", "alpha")]["s"]
        assert ("bwd", "unscoped") in rows
        assert s["scope_source_s"]["event_stat"] > 0
        assert s["scope_source_s"]["given"] == 0
        assert all(r["program"] == "jit_f" for r in s["device_rows"])

    def test_the_wire_reader_finds_what_the_compiler_wrote(self):
        meta = xprof._op_metadata(V5E_PROBE)
        table = meta["/device:TPU:0"]
        assert all(name.startswith("/device:") for name in meta)
        ops = {st["tf_op"] for st in table.values() if "tf_op" in st}
        assert "jit(f)/alpha/dot_general:" in ops
        assert "jit(f)/beta/jit(sort)/sort:" in ops
        assert all(isinstance(st.get("flops", 0), int)
                   for st in table.values())

    def test_step_annotations_name_the_idle_time(self):
        s = xprof.summarize(V5E_PROBE)
        assert s["idle_by_span"][0]["span"] == "train"
        assert s["longest_gaps"][0]["args"]["step_num"] in (0, 1)
        assert s["idle_share"] > 0.9        # a 67 us program every 12 ms


# ------------------------- the scopes the programs carry, and nothing else

def _op_names(compiled_text: str) -> set[str]:
    return set(_scopes_from_hlo(compiled_text).values())


def _scopes(compiled_text: str) -> set[tuple[str, str]]:
    return {xprof.scope_of(n) for n in _op_names(compiled_text)}


@pytest.fixture(scope="module")
def tiny_engine():
    from hyperion_tpu.models.llama import Llama, llama_tiny_config
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    model = Llama(llama_tiny_config(max_len=64, n_kv_heads=2))
    params = model.init_params(jax.random.key(0), seq=8)
    return Engine(model, {"params": params},
                  EngineConfig(slots=2, max_len=32, eos_id=None)), params


class TestScopesInPrograms:
    """The names ISSUE 24 gives the stretches of the tick, the prefill
    and the train step are in the compiled programs, and naming them
    moved no parameter."""

    ATTN = {"qkv_proj/q_proj", "qkv_proj/k_proj", "qkv_proj/v_proj", "rope",
            "kv_write", "kv_read", "attention", "o_proj"}

    def test_decode_tick(self, tiny_engine):
        eng, _ = tiny_engine
        text = eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, jnp.asarray(eng._bt),
            jnp.asarray(eng._live_mask())).compile().as_text()
        scopes = {s for _, s in _scopes(text)}
        for name in self.ATTN:
            assert f"Llama/layer_*/attn/{name}" in scopes, name
        assert {"sampling", "slot_state", "Llama/lm_head",
                "Llama/layer_*/mlp/gate_proj",
                "Llama/embed_tokens"} <= scopes
        # nothing the model runs sits bare under `attn` any more
        assert "Llama/layer_*/attn" not in scopes

    def test_prefill_and_block_copy(self, tiny_engine):
        eng, _ = tiny_engine
        text = eng._prefill_jit.lower(
            eng.model, eng.cfg.eos_id, eng.variables, eng._cache,
            eng._state, jnp.zeros((1, 16), jnp.int32),
            jnp.asarray(eng._bt[0]), jnp.int32(0), jnp.int32(0),
            jnp.int32(5), jnp.float32(0), jnp.int32(0), jnp.float32(1),
            jnp.int32(4), jax.random.key(1)).compile().as_text()
        scopes = {s for _, s in _scopes(text)}
        for name in ("kv_write", "kv_read", "attention", "rope", "o_proj"):
            assert f"Llama/layer_*/attn/{name}" in scopes, name
        assert {"sampling", "slot_state", "Llama/lm_head"} <= scopes
        zero = jnp.zeros((1,), jnp.int32)
        copy = eng._copy_jit.lower(eng._cache, zero, zero).compile()
        assert {s for _, s in _scopes(copy.as_text())} >= {"kv_copy"}

    def test_train_step(self):
        from hyperion_tpu.models.transformer_lm import (
            TransformerLM,
            gpt2_lm_config,
        )
        from hyperion_tpu.runtime.mesh import MeshSpec, make_mesh
        from hyperion_tpu.train.losses import next_token_loss
        from hyperion_tpu.train.state import (
            create_train_state,
            make_optimizer,
        )
        from hyperion_tpu.train.step import make_train_step

        cfg = gpt2_lm_config(vocab_size=128, d_model=32, n_heads=2,
                             n_layers=2, ff_dim=64, max_len=16)
        model = TransformerLM(cfg)
        opt = make_optimizer(1e-4, 0.0, 1.0)
        mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
        state, sharding = create_train_state(
            lambda r: {"params": model.init_params(r)}, opt, mesh,
            jax.random.key(0))

        def loss_fn(params, bs, batch, rngs):
            logits = model.apply({"params": params}, batch["input_ids"])
            loss = next_token_loss(logits, batch["input_ids"])
            return loss, ({"loss": loss}, bs)

        step = make_train_step(loss_fn, opt, sharding, grad_accum=2,
                               donate=False)
        batch = {"input_ids": jnp.zeros((4, 16), jnp.int32)}
        text = step.lower(state, batch, jax.random.key(1)).compile() \
            .as_text()
        scopes = _scopes(text)
        names = {s for _, s in scopes}
        # (the step's own `grad_clip`, the norm it reports, is the same
        # computation as the clip's inside the optimizer: XLA keeps one)
        assert {"loss", "optimizer", "optimizer/grad_clip",
                "grad_accum", "TransformerLM/embed/tok_emb",
                "TransformerLM/lm_head",
                "TransformerLM/block_*/attn/attention",
                "TransformerLM/block_*/attn/qkv_proj/q_proj",
                "TransformerLM/block_*/attn/o_proj",
                "TransformerLM/block_*/fc1"} <= names
        # forward and backward need no scope of their own
        assert ("bwd", "TransformerLM/block_*/attn/attention") in scopes
        assert ("bwd", "loss") in scopes
        # the clip's wrapper kept the optimizer's state a plain chain's
        plain = make_optimizer(1e-4, 0.0, 0.0)
        clipped = jax.tree.structure(opt.init(state.params))
        assert clipped.num_leaves == jax.tree.structure(
            plain.init(state.params)).num_leaves

    def test_no_parameter_path_moved(self, tiny_engine):
        from hyperion_tpu.models.transformer_lm import (
            TransformerLM,
            gpt2_lm_config,
        )

        def paths(tree):
            return {"/".join(str(k.key) for k in path)
                    for path, _ in jax.tree_util.tree_leaves_with_path(tree)}

        _, params = tiny_engine
        layer = {"attn/q_proj/kernel", "attn/k_proj/kernel",
                 "attn/v_proj/kernel", "attn/o_proj/kernel",
                 "mlp/gate_proj/kernel", "mlp/up_proj/kernel",
                 "mlp/down_proj/kernel", "input_norm/weight",
                 "post_attn_norm/weight"}
        assert paths(params) == {
            "embed_tokens/embedding", "final_norm/weight", "lm_head/kernel",
            *(f"layer_{i}/{p}" for i in range(2) for p in layer)}
        lm = TransformerLM(gpt2_lm_config(
            vocab_size=128, d_model=32, n_heads=2, n_layers=1, ff_dim=64,
            max_len=16)).init_params(jax.random.key(0))
        block = {f"{m}/{leaf}" for m in (
            "attn/q_proj", "attn/k_proj", "attn/v_proj", "attn/o_proj",
            "fc1", "fc2") for leaf in ("kernel", "bias")} \
            | {f"{ln}/{leaf}" for ln in ("ln1", "ln2")
               for leaf in ("scale", "bias")}
        assert paths(lm) == {
            "tok_emb/embedding", "pos_emb/embedding", "ln_f/scale",
            "ln_f/bias", "lm_head/kernel", "lm_head/bias",
            *(f"block_0/{p}" for p in block)}


# ------------------------------------------------- the trainer's loop

class TestTrainerSpans:
    def test_annotated_iterator_spans_each_wait(self, tmp_path):
        def slow():
            for i in range(3):
                time.sleep(0.01)
                yield i

        with profiling.capture(tmp_path):
            x = jnp.ones((64, 64))
            got = []
            for i in profiling.annotated(slow(), "train.next_batch"):
                with profiling.step_annotate("train", 40 + i):
                    got.append(int(_two_scopes(x, x)))
        assert len(got) == 3
        s = xprof.summarize(tmp_path)
        spans = {r["span"]: r for r in s["host_spans"]}
        # (the wait that finds the iterator exhausted comes after the
        # last operation: outside the window)
        assert spans["train.next_batch"]["n"] == 3
        assert spans["train.next_batch"]["median_s"] >= 0.009
        assert spans["train"]["n"] == 3
        waits = [g for g in s["longest_gaps"]
                 if g["span"] == "train.next_batch"]
        assert waits and waits[0]["s"] >= 0.009

    def test_a_profiled_epoch_leaves_the_loops_spans(self, tmp_path):
        """`--profile-dir`: every step is `train` with its global step,
        holding `train.dispatch`; the wait for the feed and the fence are
        spans too; the JSONL spans are still written."""
        from hyperion_tpu.config import Config
        from hyperion_tpu.train.trainer import train_language_model

        cfg = Config()
        cfg.train.epochs = 1
        cfg.train.batch_size = 8
        cfg.train.seq_len = 16
        cfg.train.steps_per_epoch = 3
        cfg.train.validate = False
        cfg.train.base_dir = str(tmp_path)
        cfg.train.profile_dir = str(tmp_path / "trace")
        train_language_model(cfg)
        s = xprof.summarize(tmp_path / "trace")
        spans = {r["span"]: r["n"] for r in s["host_spans"]}
        assert spans["train"] == spans["train.dispatch"] == 3
        assert spans["train.next_batch"] >= 3 and spans["train.fetch"] >= 1
        steps = sorted(g["args"]["step_num"] for g in s["longest_gaps"]
                       if "step_num" in g["args"])
        assert steps and set(steps) <= {0, 1, 2}
        assert {r["program"] for r in s["modules"]} >= {"jit_train_step"}
        jsonl = (tmp_path / "telemetry.jsonl").read_text()
        assert jsonl.count('"name":"train_step"') == 3
