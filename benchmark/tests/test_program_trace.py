"""The program's names in a trace (benchmark/lib/program_trace.py): the wire
decoder against jax's own reader, scopes from op_names and through the
HLO's data flow, the split of idle intervals over phases, and the new
per-layer readers on the fixture recorded on the chip (PR 25)."""
import glob
import hashlib
import importlib
import json
import os
import types

import pytest

from benchmark.lib import program_trace as pt, xplane
from benchmark.tests.helpers import ROOT_DIR

TESTDATA = os.path.join(ROOT_DIR, "benchmark", "lib", "testdata")
PROGRAM_FIXTURE = os.path.join(TESTDATA,
                               "serve_decode_2ticks_v5e_program.json")
OLD_FIXTURE = os.path.join(TESTDATA, "serve_decode_2ticks_v5e.json")
SERVE = ["serve_decode", "serve_longprompt"]
TRAIN = ["train_1chip", "train_pp2tp2"]
NEW_METRICS = {
    **{f"serve_idle_{p}_share": SERVE for p in pt.PHASES + ("outside",)},
    "serve_trace_overhead": SERVE,
    **{f"tick_{s}_share": SERVE for s in (
        "attention", "cache_write", "ffn", "head_sample", "layer_carry",
        "unscoped")},
    **{f"train_{s}_share": TRAIN for s in (
        "attention", "ffn", "head_loss", "optimizer", "unscoped")},
}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step_fn)/layers/while/body/closed_call/qkv/gather:", "qkv"),
    ("jit(step_fn)/layers/while/body/dynamic_slice:", "layers"),
    ("jit(step_fn)/layers/while/body/closed_call/paged_attention/"
     "pallas_call[name=paged_attention]", "paged_attention"),
    ("jit(step)/jit(main)/transpose(jvp(pipeline))/while/body/"
     "transpose(jvp(layers))/while/body/checkpoint/"
     "transpose(jvp(ffn))/dot_general", "ffn"),
    ("jit(step)/jit(main)/jvp(head_loss)/reduce_max", "head_loss"),
    ("jit(step)/jit(main)/adamw/sqrt", "adamw"),
    ("jit(step)/jit(main)/mul", ""),
    ("jit(layers_of_something)/add", ""),      # a word inside a name is none
    (None, ""),
])
def test_scope_of(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_a_gap_is_split_over_the_spans_it_runs_through():
    ms = 1e6
    spans = [("ptpu.serve.harvest", 0 * ms, 2 * ms),
             ("ptpu.serve.schedule", 3 * ms, 1 * ms),
             ("ptpu.serve.dispatch", 4 * ms, 3 * ms)]
    # one 5 ms gap from 1 to 6 ms: 1 ms of harvest, 1 ms under no span
    # (the client's loop), all of schedule, 2 ms of dispatch; and a second
    # gap wholly inside dispatch
    split = pt.split_idle([(1 * ms, 6 * ms), (6.5 * ms, 6.75 * ms)], spans)
    assert split == {"ptpu.serve.harvest": pytest.approx(1 * ms),
                     "outside": pytest.approx(1 * ms),
                     "ptpu.serve.schedule": pytest.approx(1 * ms),
                     "ptpu.serve.dispatch": pytest.approx(2.25 * ms)}
    assert sum(split.values()) == pytest.approx(5.25 * ms)
    # `xplane.reduce` would have given the whole first gap to dispatch


def test_recorded_fixture_idle_shares_add_up_to_the_idle_share():
    trace = pt.load_json(PROGRAM_FIXTURE)
    shares = pt.idle_shares(trace)
    assert set(shares) == set(pt.PHASES) | {"outside"}
    assert all(v >= 0 for v in shares.values())
    idle = xplane.idle_share_percent(xplane.reduce(trace))
    assert sum(shares.values()) == pytest.approx(idle, abs=0.05)
    # the launch has not started when dispatch returns: the device idles
    # on into `wait` (PERF.md section 6, PR 25)
    assert shares["wait"] > shares["dispatch"] > shares["harvest"] > 0


def test_recorded_fixture_spans_are_steps_with_contiguous_phases():
    trace = pt.load_json(PROGRAM_FIXTURE)
    steps = [e for e in trace["program_spans"] if e[0] == pt.STEP]
    assert [s[3]["kind"] for s in steps] == ["mixed", "decode"]
    assert [s[3]["tick"] for s in steps] == [151, 152]
    for _, start, dur, _ in steps:
        inside = [e for e in trace["program_spans"]
                  if e[0] != pt.STEP and start <= e[1] < start + dur]
        assert [e[0].rsplit(".", 1)[1] for e in inside] == list(
            pt.PHASES[:5])
        assert sum(e[2] for e in inside) >= 0.95 * dur
        for a, b in zip(inside, inside[1:]):
            assert a[1] + a[2] <= b[1]
    assert pt.step_durations_ms(trace) == pytest.approx([220.806, 81.702],
                                                        abs=1e-3)


def test_recorded_fixture_every_operation_has_a_scope():
    trace = pt.load_json(PROGRAM_FIXTURE)
    plane = "/device:TPU:0"
    by_name = {}
    for (name, _, _), scope in zip(trace["device"][plane],
                                   trace["device_scopes"][plane]):
        by_name.setdefault(name, set()).add(scope)
    # the Pallas launches carry the kernel's name whatever their number,
    # and lie under its scope
    launches = [n for n in by_name if n.startswith("paged_attention.")]
    assert len(launches) == 2
    assert all(by_name[n] == {"paged_attention"} for n in launches)
    # whole-stack copies and fills the compiler made carry no op_name;
    # through the HLO they belong to the scan's carry
    stack = [n for n in by_name if n.endswith("_bf16_16_2304_8_16_128_")
             and n.startswith(("copy.", "broadcast."))]
    assert stack and all(by_name[n] == {"layers"} for n in stack)
    shares = pt.scope_shares(trace)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares.get("", 0.0) < 5.0
    assert shares["paged_attention"] > shares["layers"] > \
        shares["cache_write"] > shares["ffn"] > 1.0


def _record(trace_file, tick_ms=(80.0, 81.0, 82.0, 220.0)):
    trace = xplane.reduce(xplane.load_json(trace_file))
    return types.SimpleNamespace(
        trace=trace, notes={"trace_file": trace_file},
        samples={"tick_ms": list(tick_ms)})


def _read(name, record):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(record)


def test_new_readers_on_the_fixture_and_on_a_program_without_names():
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # the 19 entries are the last 19, each lists its cells, and the
    # entries that were there are where they were
    assert [m["name"] for m in bench["per_layer"][-19:]] == list(NEW_METRICS)
    assert [m["name"] for m in bench["per_layer"][:13]][:2] == [
        "compiles_in_window", "tick_p50_ms"]
    for name, cells in NEW_METRICS.items():
        assert entries[name]["workloads"] == cells and \
            entries[name]["unit"] == "%"
    new, old = _record(PROGRAM_FIXTURE), _record(OLD_FIXTURE)
    no_trace = types.SimpleNamespace(trace=None, notes={}, samples={})
    serve = [n for n, cells in NEW_METRICS.items() if cells == SERVE]
    for name in serve:
        assert isinstance(_read(name, new), float), name
    for name in NEW_METRICS:
        # PR 24's fixture is a program without ptpu.* spans or scopes: a
        # reader finds nothing there, returns None and does not raise
        assert _read(name, old) is None, name
        assert _read(name, no_trace) is None, name
    idle = [_read(f"serve_idle_{p}_share", new)
            for p in pt.PHASES + ("outside",)]
    assert sum(idle) == pytest.approx(
        _read("serve_device_idle_share", new), abs=0.05)
    assert _read("tick_unscoped_share", new) < 5.0
    # p25 of the steps (81.702, 220.806) over p25 of the ticks, less one
    assert _read("serve_trace_overhead", new) == pytest.approx(
        100.0 * ((81.702 + 0.25 * (220.806 - 81.702)) / 80.75 - 1.0),
        abs=0.01)
    # the train step's scopes read the same way (no serve scope there)
    for name in (n for n, cells in NEW_METRICS.items() if cells == TRAIN):
        assert _read(name, new) in (0.0, pytest.approx(7.9556, abs=1e-3))


def test_no_file_of_the_accepted_benchmark_changed():
    """This PR's readers are new files: every file PR 24 left under
    benchmark/ still has its digest."""
    with open(os.path.join(ROOT_DIR, "benchmark", "tests", "fixtures",
                           "pr24_digests.json")) as f:
        before = json.load(f)
    for rel, digest in before.items():
        with open(os.path.join(ROOT_DIR, rel), "rb") as fh:
            assert hashlib.sha256(fh.read()).hexdigest() == digest, rel


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A profile of a jitted scan under named scopes and two annotations,
    taken here on the CPU: what the decoder must read as jax does."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("embed"):
            y = x * 2.0
        with jax.named_scope("layers"):
            def body(c, _):
                with jax.named_scope("ffn"):
                    c = jnp.tanh(c @ c)
                return c, c
            y, ys = jax.lax.scan(body, y, None, length=3)
        return y, ys

    x = jnp.ones((32, 32))
    jax.block_until_ready(f(x))
    out = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("ptpu.serve.step", tick=3) as step:
        with jax.profiler.TraceAnnotation("ptpu.serve.wait"):
            jax.block_until_ready(f(x))
        step.set_metadata(kind="decode", batch=2)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    module = f.lower(x).compile().runtime_executable().hlo_modules()[0]
    return path, module.as_serialized_hlo_module_proto()


def test_wire_decoder_reads_what_jax_reads(cpu_profile):
    import jax

    path, _ = cpu_profile
    mine = {p["name"]: p for p in pt.planes(path)}
    theirs = jax.profiler.ProfileData.from_file(path)
    for plane in theirs.planes:
        lines = dict(mine[plane.name]["lines"])
        for line in plane.lines:
            want = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events]
            got = [(n, s, d, st) for n, s, d, st, _ in lines[line.name]]
            assert len(got) == len(want)
            for g, w in zip(got, want):
                assert g[0] == w[0]
                assert g[1] == pytest.approx(w[1], abs=1.0)
                assert g[2] == pytest.approx(w[2], abs=1.0)
                assert g[3] == w[3]
    spans = [e for _, events in mine["/host:CPU"]["lines"] for e in events
             if e[0].startswith("ptpu.")]
    step = next(e for e in spans if e[0] == "ptpu.serve.step")
    assert step[3] == {"tick": 3, "kind": "decode", "batch": 2}


def test_scopes_through_the_hlo_data_flow(cpu_profile):
    _, module_proto = cpu_profile
    # HloProto.hlo_module is field 1, length-delimited
    n, prefix = len(module_proto), bytearray([0x0A])
    while True:
        prefix.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    scopes = pt.hlo_scopes(bytes(prefix) + module_proto)
    comps = pt._hlo_computations(bytes(prefix) + module_proto)
    opcodes = {i["name"]: (i["opcode"], i["op_name"])
               for _, ins in comps for i in ins}
    whiles = [n for n, (op, _) in opcodes.items() if op == "while"]
    assert whiles and all(scopes[n] == "layers" for n in whiles)
    assert "ffn" in scopes.values() and "embed" in scopes.values()
    # every instruction JAX named inside a scope keeps it; one the
    # compiler made gets a scope only from what it feeds or is fed by
    for name, (_, op_name) in opcodes.items():
        if pt.scope_of(op_name):
            assert scopes[name] == pt.scope_of(op_name)
        else:
            assert scopes[name] in ("", "embed", "layers", "ffn")
