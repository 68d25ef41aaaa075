"""Fragment-compiler equivalence + retrace-bound tests.

Asserts bit-exact parity across every simulation tier — eager per-command
``simulate``, ``simulate_jit``, bucketed/NOP-padded ``simulate_packed``,
the compiled setup-state + data-stream fast path, and vmapped batching —
on FlexASR, HLSCNN and VTA fragments, plus regression tests that the
compiled-function caches stay bounded as stream lengths vary, and that
every jitted runner's module names its ILA and role.
"""
import jax
import numpy as np
import pytest

from repro.accel import flexasr as fa, hlscnn as hc, vta as vt
from repro.core.ila import NOP_OPCODE, Command, PackedStream, bucket_length

rng = np.random.default_rng(7)


def _linear_case():
    w = (rng.standard_normal((16, 32)) * 0.1).astype(np.float32)
    b = (rng.standard_normal((16,)) * 0.1).astype(np.float32)
    frag = fa.linear_fragment(w, b)
    xs = [rng.standard_normal((6, 32)).astype(np.float32) for _ in range(2)]
    datas = [fa.pack_linear_data(frag, x) for x in xs]
    return frag, datas, fa.read_full, (slice(0, 6), slice(0, 16))


def _lstm_case():
    wi = (rng.standard_normal((32, 16)) * 0.2).astype(np.float32)
    wh = (rng.standard_normal((32, 8)) * 0.2).astype(np.float32)
    b = (rng.standard_normal((32,)) * 0.1).astype(np.float32)
    frag = fa.lstm_fragment(wi, wh, b)
    xs = [(rng.standard_normal((5, 16)) * 0.5).astype(np.float32) for _ in range(2)]
    datas = [fa.pack_lstm_data(frag, x) for x in xs]
    return frag, datas, fa.read_full, (slice(0, 5), slice(0, 8))


def _attention_case():
    frag = fa.attention_fragment(16)
    datas = [
        fa.pack_attention_data(
            frag,
            rng.standard_normal((6, 16)).astype(np.float32),
            rng.standard_normal((9, 16)).astype(np.float32),
            rng.standard_normal((9, 16)).astype(np.float32),
        )
        for _ in range(2)
    ]
    return frag, datas, fa.read_full, (slice(0, 6), slice(0, 16))


def _conv_case():
    w = (rng.standard_normal((3, 3, 4, 8)) * 0.05).astype(np.float32)
    frag = hc.conv2d_fragment(w, (8, 8, 4), (1, 1), wgt_bits=16)
    datas = [
        hc.pack_conv2d_data(frag, rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
        for _ in range(2)
    ]
    return frag, datas, hc.read_full, hc.out_slice(frag)


def _gemm_case():
    b = rng.integers(-120, 120, (12, 20)).astype(np.float32)
    frag = vt.gemm_fragment(b, 1)
    datas = [
        vt.pack_gemm_data(frag, rng.integers(-120, 120, (10, 20)).astype(np.float32))
        for _ in range(2)
    ]
    return frag, datas, vt.read_gemm_full(frag), (slice(0, 10), slice(0, 12))


def _alu_case():
    frag = vt.alu_fragment(1, 2, "add")
    datas = [
        vt.pack_alu_data(
            frag,
            rng.integers(-100, 100, (10, 24)).astype(np.float32),
            rng.integers(-100, 100, (10, 24)).astype(np.float32),
        )
        for _ in range(2)
    ]
    return frag, datas, vt.read_alu_full(frag), (slice(0, 10), slice(0, 24))


CASES = {
    "fasr_linear": _linear_case,
    "fasr_lstm": _lstm_case,
    "fasr_attention": _attention_case,
    "hlscnn_conv2d": _conv_case,
    "vta_gemm": _gemm_case,
    "vta_alu_add": _alu_case,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_all_simulation_tiers_bit_exact(name):
    """eager == jit == bucketed-padded == compiled fast path == batched."""
    frag, datas, read, window = CASES[name]()
    ila = frag.ila
    refs = []
    for data in datas:
        cmds = frag.full_commands(data)
        st_eager = ila.simulate(cmds)
        ref = np.asarray(read(st_eager))[window]
        refs.append(ref)
        # jit scan over the exact stream
        out_jit = np.asarray(read(ila.simulate_jit(cmds)))[window]
        np.testing.assert_array_equal(ref, out_jit, err_msg=f"{name}: jit != eager")
        # NOP-padded to the power-of-two bucket
        packed = PackedStream.from_commands(cmds, ila.vwidth)
        out_bucket = np.asarray(read(ila.simulate_packed(packed)))[window]
        np.testing.assert_array_equal(ref, out_bucket, err_msg=f"{name}: bucketed != eager")
        # compiled fast path: cached setup state + data stream
        out_fast = np.asarray(read(frag.run(data)))[window]
        np.testing.assert_array_equal(ref, out_fast, err_msg=f"{name}: compiled != eager")
    # batched: both samples through one vmapped call
    sts = frag.run_batch(datas)
    fulls = np.asarray(jax.vmap(read)(sts))
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(
            ref, fulls[i][window], err_msg=f"{name}: batched[{i}] != eager"
        )


def test_nop_padding_is_identity():
    x = rng.standard_normal((4, 16)).astype(np.float32)
    w = (rng.standard_normal((8, 16)) * 0.1).astype(np.float32)
    b = np.zeros((8,), np.float32)
    cmds, rd = fa.build_linear_fragment(x, w, b)
    ref = np.asarray(rd(fa.flexasr.simulate(cmds)))
    padded = cmds + [Command(NOP_OPCODE)] * 37
    out = np.asarray(rd(fa.flexasr.simulate(padded)))
    np.testing.assert_array_equal(ref, out)


def test_bucketed_retraces_bounded():
    """Scanned-stream retraces are O(log max_len): many distinct stream
    lengths map onto few power-of-two buckets."""
    before = fa.flexasr.jit_cache_info()["traces_single"]
    buckets = set()
    for n in range(1, 120, 7):
        stream = PackedStream.from_commands([Command(NOP_OPCODE)] * n, fa.V)
        fa.flexasr.simulate_packed(stream)
        buckets.add(bucket_length(n))
    after = fa.flexasr.jit_cache_info()["traces_single"]
    assert after - before <= len(buckets)
    assert len(buckets) <= 4  # lengths 1..119 -> buckets {16, 32, 64, 128}


def test_data_runner_cache_bounded_across_repeats():
    """Steady-state invocations with fixed operand shapes never recompile:
    the compiled-executor cache grows only with distinct signatures."""
    w = (rng.standard_normal((8, 16)) * 0.1).astype(np.float32)
    b = np.zeros((8,), np.float32)
    frag = fa.linear_fragment(w, b)
    frag.run(fa.pack_linear_data(frag, rng.standard_normal((4, 16)).astype(np.float32)))
    runners_before = fa.flexasr.jit_cache_info()["data_runners"]
    traces_before = fa.flexasr.jit_cache_info()["traces_single"]
    for _ in range(10):
        x = rng.standard_normal((4, 16)).astype(np.float32)
        frag.run(fa.pack_linear_data(frag, x))
    info = fa.flexasr.jit_cache_info()
    assert info["data_runners"] == runners_before
    assert info["traces_single"] == traces_before


def test_fragment_cache_reuses_setup():
    cache = fa.TARGET.fragments   # per-target cache owned by the plugin

    w = (rng.standard_normal((8, 16)) * 0.1).astype(np.float32)
    b = np.zeros((8,), np.float32)
    f1 = fa.linear_fragment(w, b)
    hits_before = cache.hits
    f2 = fa.linear_fragment(w, b)
    assert f1 is f2 and cache.hits == hits_before + 1
    # distinct parameters -> distinct fragment (content fingerprint key)
    f3 = fa.linear_fragment(w + 1.0, b)
    assert f3 is not f1


def test_executor_engines_agree():
    """Compiled Executor == seed-style jit-scan Executor == run_many."""
    from repro.core import apps
    from repro.core.codegen import Executor
    from repro.core.compile import compile_program

    expr, params = apps.build_resmlp(seed=0)
    res = compile_program(expr)
    xs_shape = next(
        v.shape for v in _vars(res.program) if v.name == "x"
    )
    X = [rng.standard_normal(xs_shape).astype(np.float32) for _ in range(3)]
    ex_c = Executor("ila", engine="compiled")
    ex_j = Executor("ila", engine="jit")
    outs_c = [np.asarray(ex_c.run(res.program, dict(params, x=x))) for x in X]
    outs_j = [np.asarray(ex_j.run(res.program, dict(params, x=x))) for x in X]
    outs_m = ex_c.run_many(res.program, [dict(params, x=x) for x in X])
    for a, b, c in zip(outs_c, outs_j, outs_m):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, np.asarray(c))


def _vars(e, seen=None):
    from repro.core import ir

    seen = set() if seen is None else seen
    if isinstance(e, ir.Var):
        yield e
    elif isinstance(e, ir.Call):
        for a in e.args:
            if id(a) not in seen:
                seen.add(id(a))
                yield from _vars(a, seen)


def test_jitted_runners_name_their_ila(caplog):
    """The compiled modules carry the ILA's name and the runner's role, so
    a profile's device operations say which simulator ran: the data
    runners and stream simulators (lowered here), and the batched reads
    and fused runners an Executor creates (from the compile log)."""
    import jax.numpy as jnp

    from repro.core import ir
    from repro.core.codegen import Executor

    frag, datas, _read, _w = _linear_case()
    ila = frag.ila
    sig, mask, rows, shared, batched = ila._host_data_batch(datas)
    st = frag.setup_state()
    low = ila._data_runner(sig, mask, True).lower(st, rows, shared, batched)
    assert "@jit_flexasr_data_batch" in low.as_text()
    one = (True,) * len(datas[0].tail)
    sh, ba = ila._split_rows(datas[0].tail.data, one)
    low = ila._data_runner(datas[0].sig(), one, False).lower(
        st, [jnp.asarray(b.rows) for b in datas[0].bulk], sh, ba)
    assert "@jit_flexasr_data_single" in low.as_text()
    stream = PackedStream.from_commands([Command(NOP_OPCODE)] * 16, fa.V)
    args = (st, stream.ops, stream.addrs, stream.data)
    assert "@jit_flexasr_stream_single" in ila.make_jit_simulator().lower(
        *args).as_text()
    assert "@jit_flexasr_stream_batch" in ila.make_batch_simulator().lower(
        st, *(a[None] for a in args[1:])).as_text()

    # weights of their own: new fragments, so new reads and fused runners
    r = np.random.default_rng(23)
    w = (r.standard_normal((12, 20)) * 0.1).astype(np.float32)
    b = (r.standard_normal((12,)) * 0.1).astype(np.float32)
    prog = ir.call("vta_relu", ir.call(
        "fasr_linear", ir.Var("x", (3, 20)), ir.Var("w", w.shape),
        ir.Var("b", b.shape)))
    envs = [{"x": r.standard_normal((3, 20)).astype(np.float32), "w": w, "b": b}
            for _ in range(3)]
    caplog.set_level("WARNING", logger="jax._src.interpreters.pxla")
    with jax.log_compiles():
        for engine in ("pipelined", "fused"):
            Executor("ila", engine=engine).run_many(prog, envs)
    compiled = {m.split("jit(", 1)[1].split(")", 1)[0]
                for m in caplog.messages if m.startswith("Compiling jit(")}
    assert {"flexasr_read", "vta_read", "flexasr_fused_linear"} <= compiled
    assert not compiled & {"run", "run_single", "run_batch", "read",
                           "read_full", "read_out"}
