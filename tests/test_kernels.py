"""Per-kernel correctness: shape/dtype sweeps, Pallas interpret mode vs the
pure-jnp oracle. Integer kernels must match bit-exactly; float kernels to
tight tolerances."""

import numpy as np
import pytest

from repro.kernels import ops, ref


# -- int8 GEMM ------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [
    (8, 16, 8), (128, 128, 128), (100, 300, 180), (1, 512, 64),
    (257, 129, 65), (64, 1024, 256),
])
def test_gemm_int8_sweep(rng, M, K, N):
    x = rng.integers(-128, 128, (M, K)).astype(np.int8)
    w = rng.integers(-128, 128, (K, N)).astype(np.int8)
    out = ops.gemm_int8(x, w, backend="interpret")
    expect = x.astype(np.int32) @ w.astype(np.int32)
    assert np.array_equal(np.asarray(out), expect)


@pytest.mark.parametrize("blocks", [dict(bm=32, bn=32, bk=32),
                                    dict(bm=128, bn=128, bk=64)])
def test_gemm_int8_requant(rng, blocks):
    M, K, N = 96, 160, 144
    x = rng.integers(-128, 128, (M, K)).astype(np.int8)
    w = rng.integers(-128, 128, (K, N)).astype(np.int8)
    mult = (rng.random(N) * 0.001 + 1e-5).astype(np.float32)
    out = ops.gemm_int8(x, w, mult, backend="interpret", **blocks)
    expect = ref.gemm_int8(x, w, mult)
    assert np.array_equal(np.asarray(out), np.asarray(expect))
    assert out.dtype == np.int8


def test_gemm_requant_round_half_even_epilogue():
    """The fused epilogue rounds halves to even — the exact contract of
    kernels.ref, executor._requant_np (np.round), and quantize.requantize.
    acc * 0.5 produces exact .5 halves for odd accumulators: banker's
    rounding sends 0.5 -> 0, 1.5 -> 2, 2.5 -> 2, -0.5 -> 0, -1.5 -> -2."""
    x = np.ones((1, 1), np.int8)
    w = np.array([[1, 3, 5, -1, -3, 7, 2]], np.int8)     # odd + even accs
    mult = np.float32(0.5)
    out = ops.gemm_int8(x, w, mult, backend="interpret", bm=8, bn=8, bk=8)
    expect = np.array([[0, 2, 2, 0, -2, 4, 1]], np.int8)
    assert np.array_equal(np.asarray(out), expect)
    # and the oracle chain agrees with itself
    from repro.core.executor import _requant_np
    acc = x.astype(np.int32) @ w.astype(np.int32)
    assert np.array_equal(_requant_np(acc, mult), expect)
    assert np.array_equal(np.asarray(ref.gemm_int8(x, w,
                                                   np.full(7, mult))), expect)


def test_gemm_requant_scalar_mult_broadcast(rng):
    """Scalar multipliers (what init_params produces) broadcast in the
    kernel epilogue exactly like a per-channel vector."""
    M, K, N = 33, 65, 17
    x = rng.integers(-128, 128, (M, K)).astype(np.int8)
    w = rng.integers(-128, 128, (K, N)).astype(np.int8)
    mult = np.float32(0.003)
    out = ops.gemm_int8(x, w, mult, backend="interpret", bm=16, bn=16,
                        bk=16)
    expect = ref.gemm_int8(x, w, np.full(N, mult, np.float32))
    assert np.array_equal(np.asarray(out), np.asarray(expect))


# -- conv2d implicit im2col --------------------------------------------------------

@pytest.mark.parametrize("H,W,C,N,k,stride,pad", [
    (16, 16, 3, 8, 3, 1, 1),
    (17, 19, 6, 24, 3, 2, 1),
    (14, 14, 8, 16, 1, 1, 0),
    (32, 20, 4, 32, 5, 2, 2),
    (9, 9, 16, 8, 7, 1, 3),
])
def test_conv2d_sweep(rng, H, W, C, N, k, stride, pad):
    x = rng.integers(-128, 128, (H, W, C)).astype(np.int8)
    w = rng.integers(-128, 128, (k * k * C, N)).astype(np.int8)
    out = ops.conv2d_int8(x, w, kh=k, kw=k, stride=stride, padding=pad,
                          backend="interpret")
    expect = ref.conv2d_int8(x, w, stride=stride, padding=pad)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("per_channel", [False, True])
def test_conv2d_fused_requant(rng, per_channel):
    """conv2d kernel with the requant epilogue fused == ref conv + requant
    (bit-exact, interpret mode)."""
    H, W, C, N, k = 17, 15, 5, 12, 3
    x = rng.integers(-128, 128, (H, W, C)).astype(np.int8)
    w = rng.integers(-128, 128, (k * k * C, N)).astype(np.int8)
    if per_channel:
        mult = (rng.random(N) * 0.002 + 1e-5).astype(np.float32)
    else:
        mult = np.float32(0.001)
    out = ops.conv2d_int8(x, w, mult, kh=k, kw=k, stride=2, padding=1,
                          backend="interpret", rows_t=4, bn=8)
    expect = ref.conv2d_int8(x, w, stride=2, padding=1, requant_mult=mult)
    assert out.dtype == np.int8
    assert np.array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("requant", [True, False])
@pytest.mark.parametrize("H,W,N,k,stride,pad", [
    (16, 20, 32, 6, 2, 2),    # YOLOv5s's 6×6/2, one column block
    (24, 48, 32, 6, 2, 2),    # two column blocks, the second partial
    (14, 14, 64, 7, 2, 3),    # ResNet-50's 7×7/2, rows of 84 lanes
    (12, 44, 64, 7, 2, 3),    # rows of 264 lanes: a partial 128-lane row
    (10, 12, 16, 3, 1, 1),    # stride 1: row groups of one frame row
])
def test_stem_kernel_bit_exact(rng, H, W, N, k, stride, pad, requant, batch):
    """The stem kernel over row groups of 3-channel frames == the
    reference conv (and its requant) bit for bit, with a fused requant and
    with an int32 output, for one frame and vmapped over 4."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import stem_conv as S
    C = 3
    x = rng.integers(-128, 128, (batch, H, W, C)).astype(np.int8)
    w = rng.integers(-128, 128, (k * k * C, N)).astype(np.int8)
    mult = (rng.random(N) * 0.002 + 1e-5).astype(np.float32) \
        if requant else None
    geom = S.stem_geometry(H, W, C, N, kh=k, kw=k, stride=stride,
                           padding=pad)
    assert geom.lanes == stride * W * C
    xs = x.reshape((batch,) + S.stem_input_shape(H, W, C, stride))
    fn = jax.vmap(lambda v: S.stem_conv_pallas(
        v, jnp.asarray(S.stem_bands(w, geom)),
        None if mult is None else jnp.asarray(mult), geom=geom,
        interpret=True))
    out = np.asarray(fn(jnp.asarray(xs)))
    assert out.dtype == (np.int8 if requant else np.int32)
    for b in range(batch):
        expect = ref.conv2d_int8(x[b], w, stride=stride, padding=pad,
                                 requant_mult=mult)
        assert out[b].shape == expect.shape
        assert np.array_equal(out[b], np.asarray(expect))


# -- int8 SiLU in the epilogues -----------------------------------------------

def _silu_through(kernel: str, q: np.ndarray, scale: float) -> np.ndarray:
    """Every int8 value `q` (256 of them) through `kernel`'s requant and
    SiLU epilogue: an identity contraction (one weight of 1, the rest 0)
    and a multiplier of 1, so the epilogue reads q itself."""
    import jax.numpy as jnp
    from repro.core.quantize import silu_steps
    from repro.kernels import stem_conv as S
    from repro.kernels.conv2d_im2col import conv2d_int8_pallas
    from repro.kernels.gemm_int8 import gemm_int8_pallas
    silu, one = silu_steps(scale), jnp.float32(1.0)
    if kernel == "gemm":                  # (256, 8) @ (8, 128)
        x = np.zeros((256, 8), np.int8)
        x[:, 0] = q
        w = np.zeros((8, 128), np.int8)
        w[0] = 1
        out = gemm_int8_pallas(x, w, one, bm=64, bn=128, bk=128, silu=silu,
                               interpret=True)
        return np.asarray(out)[:, 0]
    if kernel == "conv":                  # 3x3/1 over 16x16x1, centre tap
        w = np.zeros((9, 8), np.int8)
        w[4] = 1
        out = conv2d_int8_pallas(q.reshape(16, 16, 1), w, one, kh=3, kw=3,
                                 padding=1, rows_t=4, bn=8, silu=silu,
                                 interpret=True)
        return np.asarray(out)[..., 0].reshape(-1)
    # stem: 6x6/2 over 32x32x3; out[i, j] = frame[2i, 2j, 0]
    frame = np.zeros((32, 32, 3), np.int8)
    frame[::2, ::2, 0] = q.reshape(16, 16)
    w = np.zeros((6 * 6 * 3, 32), np.int8)
    w[(2 * 6 + 2) * 3] = 1
    geom = S.stem_geometry(32, 32, 3, 32, kh=6, kw=6, stride=2, padding=2)
    out = S.stem_conv_pallas(
        jnp.asarray(frame.reshape(S.stem_input_shape(32, 32, 3, 2))),
        jnp.asarray(S.stem_bands(w, geom)), one, geom=geom, silu=silu,
        interpret=True)
    return np.asarray(out)[..., 0].reshape(-1)


def _silu_fused_segment(q: np.ndarray, scale: float, fold: bool):
    """A fused megakernel segment (a 3x3 conv over 64 channels, too wide
    for the stem kernel, its requant and SiLU) over every int8 value, run
    in interpret mode. With `fold` False the requant's output is a graph
    output too, so the SiLU stays a step of its own inside the
    segment."""
    from repro.core import analyze, lower_program, run_megakernel
    from repro.core import compiled as C
    from repro.core import megakernel as MK
    from repro.core.graph import Graph, conv2d, requant, silu
    from repro.hw import scaled_paper_machine
    g = Graph("silu_seg")
    g.add_tensor("input", (16, 16, 64), "int8", is_input=True)
    y = requant(g, "c.rq", conv2d(g, "c", "input", 8, 3))
    if not fold:
        g.mark_output(y)
    g.mark_output(silu(g, "c.silu", y, scale))
    g.validate()
    hw = scaled_paper_machine(4)
    _, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    w = np.zeros((9 * 64, 8), np.int8)
    w[4 * 64] = 1                        # centre tap, channel 0
    prog = lower_program(g, {"c.w": w, "c.rq.mult": np.float32(1.0)},
                         subtasks, mapping, sched, hw=hw)
    (seg,) = MK.plan_segments(prog)
    assert seg.kind == "fused"
    counts = C.plan_counts(prog)
    assert (counts["silu_epilogue"], counts["silu_steps"]) == \
        ((1, 0) if fold else (0, 1))
    x = np.zeros((16, 16, 64), np.int8)
    x[..., 0] = q.reshape(16, 16)
    out = run_megakernel(prog, {"input": x}, interpret=True)
    return out["c.silu.out"][..., 0].reshape(-1)


@pytest.mark.parametrize("scale", [1 / 8, 1 / 16, 1 / 32])
@pytest.mark.parametrize("kernel", ["gemm", "conv", "stem", "fused",
                                    "fused_step"])
def test_silu_epilogue_reproduces_the_table(kernel, scale):
    """Each kernel epilogue that applies the int8 SiLU (GEMM, windowed
    conv, stem, a fused megakernel segment, and a SiLU left as a step of
    its own in a fused segment) maps all 256 int8 inputs to the host's
    float64 table (`quantize.silu_table`) bit for bit."""
    from repro.core.quantize import silu_steps, silu_table
    q = np.arange(-128, 128).astype(np.int8)
    if kernel.startswith("fused"):
        got = _silu_fused_segment(q, scale, fold=kernel == "fused")
    else:
        got = _silu_through(kernel, q, scale)
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got, silu_table(scale)[q.astype(int) + 128])
    assert len(silu_steps(scale)[1]) == {1 / 8: 8, 1 / 16: 16, 1 / 32: 32}[scale]


def test_spm_derived_blocks_fit_scratchpad():
    """hw.derive_*_blocks always return shapes whose working set (with
    double buffering on dual-ported machines) fits the scratchpad."""
    from repro.hw import (PAPER_RISCV, TPU_V5E, derive_conv_blocks,
                          derive_gemm_blocks, scaled_paper_machine)
    conv_attrs = {"H": 64, "W": 64, "C_in": 32, "C_out": 64, "kh": 3,
                  "kw": 3, "stride": 1, "padding": 1}
    for hw in (PAPER_RISCV, TPU_V5E, scaled_paper_machine(4),
               scaled_paper_machine(16, scratchpad_bytes=64 * 1024)):
        for out_bytes in (1, 4):
            bm, bn, bk = derive_gemm_blocks(hw, 4096, 1024, 512, out_bytes)
            stream = (bm * bk + bk * bn) * (2 if hw.dual_ported else 1)
            assert stream + bm * bn * (4 + out_bytes) <= hw.scratchpad_bytes
            rows_t, cbn = derive_conv_blocks(hw, conv_attrs, out_bytes)
            assert rows_t >= 1 and cbn >= 1
    # the paper machine's 1 MiB scratchpad yields the paper-scale GEMM tile
    assert derive_gemm_blocks(PAPER_RISCV, 4096, 1024, 512) == (256,) * 3


def test_conv2d_matches_core_executor(rng):
    """Kernel oracle == repro.core.executor im2col semantics."""
    from repro.core.executor import im2col
    H, W, C, N, k = 12, 12, 5, 7, 3
    x = rng.integers(-128, 128, (H, W, C)).astype(np.int8)
    w = rng.integers(-128, 128, (k * k * C, N)).astype(np.int8)
    cols = im2col(x, k, k, 1, 1)
    expect = (cols.astype(np.int32) @ w.astype(np.int32)).reshape(
        H, W, N)
    out = ref.conv2d_int8(x, w, stride=1, padding=1)
    assert np.array_equal(np.asarray(out), expect)


# -- flash attention ----------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window", [
    (1, 4, 4, 64, 64, 32, True, None),
    (2, 8, 2, 100, 100, 64, True, None),
    (2, 8, 2, 100, 100, 64, True, 37),
    (1, 4, 1, 33, 77, 16, True, None),       # decode-ish offset
    (2, 4, 4, 64, 64, 32, False, None),
    (2, 8, 2, 1, 100, 64, True, None),       # single-token decode
])
def test_flash_attention_sweep(rng, B, Hq, Hkv, Sq, Skv, D, causal,
                               window):
    q = rng.standard_normal((B, Hq, Sq, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, Skv, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, Skv, D)).astype(np.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              backend="interpret", bq=32, bk=32)
    expect = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=1e-4)


def test_blockwise_attention_matches_oracle(rng):
    from repro.models.attention import attention_blockwise
    B, Hq, Hkv, S, D = 2, 4, 2, 200, 32
    q = rng.standard_normal((B, Hq, S, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    for window in (None, 50):
        out = attention_blockwise(q, k, v, causal=True, window=window,
                                  q_chunk=64, kv_chunk=48)
        expect = ref.flash_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=3e-5, rtol=1e-4)


# -- ssm scan -----------------------------------------------------------------------

@pytest.mark.parametrize("B,T,D,ct", [
    (1, 16, 8, 4), (2, 100, 32, 16), (2, 128, 64, 128), (3, 33, 16, 8),
])
def test_ssm_scan_sweep(rng, B, T, D, ct):
    a = (rng.random((B, T, D)) * 0.9 + 0.05).astype(np.float32)
    x = rng.standard_normal((B, T, D)).astype(np.float32)
    seq = ref.ssm_scan_sequential(a, x)
    assoc = ref.ssm_scan(a, x)
    pall = ops.ssm_scan(a, x, backend="interpret", ct=ct)
    np.testing.assert_allclose(np.asarray(assoc), np.asarray(seq),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(pall), np.asarray(seq),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,bq,bk", [
    (1, 4, 4, 64, 64, 32, 16, 16),
    (2, 8, 2, 100, 100, 64, 32, 64),         # GQA, uneven blocks
    (1, 4, 1, 33, 77, 16, 8, 32),            # decode offset
    (2, 8, 2, 1, 100, 64, 128, 32),          # single-token decode
])
def test_flash_attention_scale_and_blocks(rng, B, Hq, Hkv, Sq, Skv, D,
                                          bq, bk):
    """Pallas flash attention == oracle across block shapes, with and
    without a custom logit scale (the `scale` operand the serving path
    forwards)."""
    q = rng.standard_normal((B, Hq, Sq, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, Skv, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, Skv, D)).astype(np.float32)
    for scale in (None, 0.25):
        out = ops.flash_attention(q, k, v, causal=True, scale=scale,
                                  backend="interpret", bq=bq, bk=bk)
        expect = ref.flash_attention(q, k, v, causal=True, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("B,T,D,ct", [
    (1, 16, 8, 4), (2, 100, 32, 16), (3, 33, 16, 8), (2, 37, 8, 128),
])
def test_ssm_scan_h0_carry(rng, B, T, D, ct):
    """The h0 operand seeds the recurrence carry (the decode-resume path):
    the Pallas kernel == sequential oracle for a nonzero initial state,
    including T not a multiple of the chunk and ct > T."""
    a = (rng.random((B, T, D)) * 0.9 + 0.05).astype(np.float32)
    x = rng.standard_normal((B, T, D)).astype(np.float32)
    h0 = rng.standard_normal((B, D)).astype(np.float32)
    seq = ref.ssm_scan_sequential(a, x, h0)
    pall = ops.ssm_scan(a, x, h0, backend="interpret", ct=ct)
    np.testing.assert_allclose(np.asarray(pall), np.asarray(seq),
                               atol=1e-4, rtol=1e-4)
    # and continuity: scanning [0:t) then resuming from its last state
    # equals one scan over [0:T)
    t = T // 2
    y1 = ops.ssm_scan(a[:, :t], x[:, :t], h0, backend="interpret", ct=ct)
    y2 = ops.ssm_scan(a[:, t:], x[:, t:], np.asarray(y1)[:, -1],
                      backend="interpret", ct=ct)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(seq)[:, t:],
                               atol=1e-4, rtol=1e-4)


def test_resolve_backend_drives_model_attend(rng):
    """`models.attention.attend` routes through the kernel backend
    resolution: forcing the interpret backend runs the Pallas kernel and
    matches the ref path used on CPU."""
    from repro.models.attention import attend
    B, Hq, Hkv, S, D = 1, 4, 2, 48, 16
    q = rng.standard_normal((B, Hq, S, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    assert ops.resolve_backend() == "ref"        # CPU CI default
    expect = np.asarray(attend(q, k, v, causal=True))
    ops.set_default_backend("interpret")
    try:
        assert ops.resolve_backend() == "interpret"
        out = np.asarray(attend(q, k, v, causal=True))
    finally:
        ops.set_default_backend("auto")
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=1e-4)


def test_ssm_block_decode_uses_dispatch(rng):
    """models.ssm decode path goes through ops.ssm_scan: forcing the
    interpret backend keeps the block's decode output unchanged."""
    import jax
    import jax.numpy as jnp
    from repro.models.config import ModelConfig
    from repro.models.ssm import ssm_apply, ssm_init
    cfg = ModelConfig(name="t", family="ssm", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=32,
                      ssm_state=4)
    p = ssm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
    state = jnp.asarray(
        rng.standard_normal((2, 32, 4)).astype(np.float32))
    y_ref, _ = ssm_apply(p, x, cfg, state=state)
    ops.set_default_backend("interpret")
    try:
        y_int, _ = ssm_apply(p, x, cfg, state=state)
    finally:
        ops.set_default_backend("auto")
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)


def test_wkv_chunked_matches_sequential(rng):
    """RWKV6 chunked WKV == step-by-step recurrence."""
    import jax.numpy as jnp
    from repro.models.rwkv import wkv_chunked
    B, H, T, dk, dv = 2, 3, 50, 8, 8
    r = rng.standard_normal((B, H, T, dk)).astype(np.float32)
    k = rng.standard_normal((B, H, T, dk)).astype(np.float32)
    v = rng.standard_normal((B, H, T, dv)).astype(np.float32)
    w = (rng.random((B, H, T, dk)) * 0.5 + 0.5).astype(np.float32)
    u = rng.standard_normal((H, dk)).astype(np.float32)
    y, S_fin = wkv_chunked(jnp.asarray(r), jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(w), jnp.asarray(u), chunk=16)
    # sequential reference
    S = np.zeros((B, H, dk, dv), np.float64)
    ys = np.zeros((B, H, T, dv), np.float64)
    for t in range(T):
        kv = np.einsum("bhk,bhv->bhkv", k[:, :, t], v[:, :, t])
        ys[:, :, t] = np.einsum(
            "bhk,bhkv->bhv", r[:, :, t],
            S + u[None, :, :, None] * kv)
        S = w[:, :, t][..., None] * S + kv
    np.testing.assert_allclose(np.asarray(y), ys, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(S_fin), S, atol=2e-3, rtol=2e-3)
