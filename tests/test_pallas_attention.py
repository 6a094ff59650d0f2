"""Fused Pallas attention: parity vs the XLA einsum path (interpret mode on
CPU; the same kernel compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops.attention import MultiHeadAttention, _dot_product_attention
from perceiver_io_tpu.ops.pallas_attention import (
    fused_attention,
    seq_parallel_fused_attention,
)


def _rand(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(0, 1, shape), dtype=dtype)


def _xla(q, k, v, pad_mask=None):
    return _dot_product_attention(
        q, k, v, pad_mask, None, 0.0, None, True
    )


@pytest.fixture
def lane_aligned():
    """Force the COMPILED lane alignment while kernels run interpreted, so
    the fuzz classes resolve blocks exactly as hardware does (the
    pallas_attention._TEST_ALIGNMENT hook)."""
    import perceiver_io_tpu.ops.pallas_attention as pa

    pa._TEST_ALIGNMENT = 128
    yield
    pa._TEST_ALIGNMENT = None


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("t,s", [(16, 64), (8, 30)])
def test_matches_xla_path(rng, masked, t, s):
    b, h, d = 2, 2, 8
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = jnp.asarray(rng.random((b, s)) < 0.3) if masked else None
    out = fused_attention(q, k, v, pad_mask, kv_block_size=16)
    ref = _xla(q, k, v, pad_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_kv_streaming_multiblock(rng):
    """Online softmax across many KV blocks equals single-pass softmax."""
    b, t, s, h, d = 1, 4, 128, 1, 8
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    blocked = fused_attention(q, k, v, kv_block_size=16)  # 8 blocks
    single = fused_attention(q, k, v, kv_block_size=128)  # 1 block
    ref = _xla(q, k, v)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(single), atol=1e-6)


def test_padding_path(rng):
    """S with no good divisor gets padded with masked keys — results equal."""
    b, t, s, h, d = 2, 4, 17, 1, 8
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = jnp.zeros((b, s), bool).at[:, -3:].set(True)
    out = fused_attention(q, k, v, pad_mask, kv_block_size=4)  # pads 17 → 20
    ref = _xla(q, k, v, pad_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_padding_path_fully_masked_row(rng):
    """Kernel-padded keys must stay excluded even when a row is fully masked
    (the uniform softmax covers only the real S keys, as on the XLA path)."""
    b, t, s, h, d = 1, 4, 17, 1, 8
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = jnp.ones((b, s), bool)
    out = fused_attention(q, k, v, pad_mask, kv_block_size=4)  # pads 17 → 20
    ref = _xla(q, k, v, pad_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_block_size_selection():
    from perceiver_io_tpu.ops.pallas_attention import _kv_block_size

    # TPU alignment: blocks must be multiples of 128 (or the full dim)
    assert _kv_block_size(4096, 512, 128) == 512
    assert _kv_block_size(512, 512, 128) == 512  # single full block
    assert _kv_block_size(1000, 512, 128) == 0  # no aligned divisor → pad/full
    assert _kv_block_size(1024, 768, 128) == 512  # largest aligned divisor
    # interpret mode: any divisor goes
    assert _kv_block_size(30, 16, 1) == 15
    assert _kv_block_size(17, 16, 1) == 0


def test_auto_q_block_resolution():
    """The q_block auto-default (None) resolves AFTER s_blk, inside its
    measured-safe regime ONLY: resolved s_blk·d within the 256x512 compile
    boundary AND T dividing the big block exactly (PERF.md r3 sweep — both
    guards are load-bearing; the (t_blk 1024, s_blk 512, d 512) combo is a
    measured scoped-VMEM OOM)."""
    import jax.numpy as jnp

    from perceiver_io_tpu.ops import pallas_attention as pa

    def resolve(t, s, d, kv_block=pa.DEFAULT_KV_BLOCK, q_block=None):
        q = jnp.zeros((1, t, 1, d), jnp.bfloat16)
        k = jnp.zeros((1, s, 1, d), jnp.bfloat16)
        bias = jnp.zeros((1, s), jnp.float32)
        _, _, _, _, t_blk, s_blk, _ = pa._prepare_blocks(
            q, k, k, bias, kv_block, q_block, interpret=False
        )
        return t_blk, s_blk

    # flow encoder-cross-like (S has a 256 divisor): safe → big query block
    t_blk, s_blk = resolve(2048, 182528, 512)
    assert (t_blk, s_blk) == (1024, 256)
    # same T/S but s_blk resolves to 512 (S divisible by 512): s_blk·d over
    # the measured boundary at d=512 → stays at the 512 default
    t_blk, s_blk = resolve(2048, 8192, 512)
    assert (s_blk, t_blk) == (512, 512)
    # shallow heads keep the bump at s_blk 512 (s_blk·d = 512·128 is safe)
    t_blk, s_blk = resolve(2048, 8192, 128)
    assert (s_blk, t_blk) == (512, 1024)
    # T not divisible by the big block (would pad / widen the full-residency
    # fallback — unmeasured) → 512 default
    t_blk, _ = resolve(1152, 182528, 128)
    assert t_blk != 1024
    # head dims past the sweep's measured range (d > 512) stay on the 512
    # default even when s_blk·d is small — the 1024-row query block + f32
    # accumulator at d=1024 is an unmeasured VMEM regime
    t_blk, s_blk = resolve(2048, 182528, 1024, kv_block=128)
    assert s_blk * 1024 <= pa.LONG_KV_SAFE_SBLK_D and t_blk == 512
    # explicit q_block_size is always honored
    t_blk, _ = resolve(2048, 182528, 512, q_block=512)
    assert t_blk == 512


def test_auto_kv_block_resolution():
    """``kv_block_size=None`` widens KV streaming for shallow heads at long S
    (PERF.md r3 kv sweep) and caps the q bump by the measured probs-area
    compile boundary — deep heads and short S keep the 512 default."""
    import jax.numpy as jnp

    from perceiver_io_tpu.ops import pallas_attention as pa

    def resolve(t, s, d):
        q = jnp.zeros((1, t, 1, d), jnp.bfloat16)
        k = jnp.zeros((1, s, 1, d), jnp.bfloat16)
        bias = jnp.zeros((1, s), jnp.float32)
        _, _, _, _, t_blk, s_blk, _ = pa._prepare_blocks(
            q, k, k, bias, None, None, interpret=False
        )
        return t_blk, s_blk

    # long-context MLM cross shape: d=16 streams 2048-wide KV blocks
    assert resolve(256, 131072, 16) == (256, 2048)
    # ... and the auto q bump is CAPPED by the probs-area boundary
    # (t 1024 × s 2048 is the measured OOM; kv 2048 + q 512 measured fastest)
    assert resolve(1024, 131072, 16) == (512, 2048)
    # mid-depth heads (ImageNet 8-head): 2048-wide KV requested (r5 re-sweep:
    # 2048 wins 3-12% across in-8h and the TPU-width long-context shapes);
    # 50176 = 1792·28 has no aligned divisor at 2048 itself, so the divisor
    # rule lands on 1792 (≥ half the request — no padding needed)
    assert resolve(512, 50176, 128) == (512, 1792)
    # deep heads keep 512 — flow encoder-cross resolution is UNCHANGED
    # (s_blk 256 from S's divisor structure, q bump still applies)
    assert resolve(2048, 182528, 512) == (1024, 256)
    # short S resolves to its full dim / divisor exactly as an explicit
    # request would (no widening possible at S = 512)
    assert resolve(256, 512, 16)[1] == 512
    # mid-S shallow shapes widen too: flow-self (d=64, S=2048) streams the
    # whole KV in one block per grid step (measured 1.34 → 0.98 ms)
    assert resolve(2048, 2048, 64) == (512, 2048)
    # S with no lane-aligned divisor INSIDE the widened full-residency
    # window keeps the tuned 512 padding path (a widened block would pull
    # s_blk = s = 7000 full residency into unmeasured probs territory) ...
    t_blk, s_blk = resolve(256, 7000, 16)
    assert s_blk <= 512
    # ... but beyond that window (s > 4·kv) the pad-to-block path is safe
    # and keeps the widened block
    assert resolve(256, 12000, 16)[1] == 2048
    # the guard evaluates against the POST-shrink kv: t=904 forces the probs
    # loop to halve 2048 -> 1024, and 2816 has a divisor for 2048 (1408) but
    # none for 1024 — the shrunk block's full-residency window would pull
    # s_blk = 2816 (2.43M-element probs, past the measured OOM) without it
    t_blk, s_blk = resolve(904, 2816, 16)
    assert t_blk * s_blk <= pa.LONG_KV_SAFE_PROBS * 2  # old default path
    assert s_blk <= 512
    # seq-parallel shard-local slices resolve on the LOCAL length
    assert resolve(256, 131072 // 8, 16) == (256, 2048)
    # a query count with no aligned divisor takes the full-residency
    # t_blk = t fallback — the kv widening must shrink so t_blk·s_blk stays
    # inside the measured probs-area boundary (904·2048 would exceed it)
    assert resolve(904, 131072, 16) == (904, 1024)
    # divisible T is unaffected by that bound (t_blk 512 resolves normally)
    assert resolve(1024, 131072, 16) == (512, 2048)

    def resolve_q(t, s, d, q_block):
        q = jnp.zeros((1, t, 1, d), jnp.bfloat16)
        k = jnp.zeros((1, s, 1, d), jnp.bfloat16)
        bias = jnp.zeros((1, s), jnp.float32)
        _, _, _, _, t_blk, s_blk, _ = pa._prepare_blocks(
            q, k, k, bias, None, q_block, interpret=False
        )
        return t_blk, s_blk

    # an EXPLICIT big query block bypasses the auto q-bump guard, so the kv
    # widening itself must shrink to keep t_blk·s_blk inside the boundary
    # (1024×2048 is the measured OOM; 1024×1024 compiles — measured 8.17 ms)
    assert resolve_q(1024, 131072, 16, q_block=1024) == (1024, 1024)


def test_fully_masked_row_uniform(rng):
    """A fully padded sequence softmaxes to uniform — XLA-path parity, no NaN."""
    b, t, s, h, d = 2, 4, 8, 1, 4
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = jnp.zeros((b, s), bool).at[0].set(True)  # row 0 fully masked
    out = fused_attention(q, k, v, pad_mask, kv_block_size=8)
    ref = _xla(q, k, v, pad_mask)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_bfloat16(rng):
    b, t, s, h, d = 2, 8, 32, 2, 8
    q, k, v = (_rand(rng, b, n, h, d, dtype=jnp.bfloat16) for n in (t, s, s))
    out = fused_attention(q, k, v, kv_block_size=16)
    ref = _xla(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


def _kernel_calls(jaxpr, name):
    """Times ``jaxpr`` (sub-jaxprs included) calls the pallas_call named
    ``name``; a kernel's own body is not searched."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += eqn.params["name"] == name
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += _kernel_calls(sub, name)
    return count


# b, t, s, h, d, padded share of the keys, causal_offset, kv_block, q_block
GRADIENT_CASES = {
    "causal_tile_skipping": (2, 32, 32, 2, 8, 0.0, 0, 16, 8),
    "causal_pad_mask": (2, 32, 32, 2, 8, 0.25, 0, 16, 8),
    "non_causal": (2, 16, 32, 2, 8, 0.0, None, 16, 8),
    "pad_mask": (2, 4, 32, 2, 8, 0.25, None, 16, None),
    "query_blocks": (1, 12, 24, 1, 8, 0.0, None, 8, 4),
}


@pytest.mark.parametrize("case", list(GRADIENT_CASES))
def test_gradients_and_kept_residuals(rng, case, capsys):
    """Two attention layers in a row, three ways: no checkpoint, each layer
    under a bare ``jax.checkpoint``, each under one whose policy keeps the
    kernel's named residuals. The gradients match the XLA path's and are the
    same bits all three ways; the bare checkpoint runs the forward kernel
    twice a layer, the policy once (the recomputation holds none), the two
    backward kernels once either way; what the policy keeps is the output and
    ONE float a row of each statistic."""
    import perceiver_io_tpu.ops.pallas_attention as pa

    b, t, s, h, d, padded, offset, kv_blk, q_blk = GRADIENT_CASES[case]
    layers = 2
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = None
    if padded:
        # key 0 stays: under the causal rule every row then sees a key
        pad_mask = jnp.asarray(rng.random((b, s)) < padded).at[:, 0].set(False)
    above = (None if offset is None
             else jnp.arange(s)[None, :] > jnp.arange(t)[:, None] + offset)

    def fused(x, k, v):
        return x + fused_attention(x, k, v, pad_mask, kv_block_size=kv_blk,
                                   q_block_size=q_blk, causal_offset=offset)

    def xla(x, k, v):
        return x + _dot_product_attention(x, k, v, pad_mask, above, 0.0, None, True)

    def loss(layer):
        def fn(x, k, v):
            for _ in range(layers):
                x = layer(x, k, v)
            return jnp.sum(x ** 2)
        return fn

    def grad(layer):
        return jax.grad(loss(layer), argnums=(0, 1, 2))

    keeping = jax.checkpoint(fused, policy=jax.checkpoint_policies.save_only_these_names(
        pa.REMAT_FUSED_OUT, pa.REMAT_FUSED_STATS))
    grads = {"none": grad(fused), "bare": grad(jax.checkpoint(fused)),
             "policy": grad(keeping)}
    calls = {
        name: [_kernel_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr, kernel)
               for kernel in (pa.KERNEL_FWD, pa.KERNEL_DQ, pa.KERNEL_DKV)]
        for name, grad in grads.items()}
    assert calls == {"none": [layers] * 3, "bare": [2 * layers, layers, layers],
                     "policy": [layers] * 3}

    got = {name: grad(q, k, v) for name, grad in grads.items()}
    for g_fused, g_xla in zip(got["none"], grad(xla)(q, k, v)):
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_xla), atol=2e-5)
    for name in ("bare", "policy"):
        for g, g_none in zip(got[name], got["none"]):
            assert np.array_equal(np.asarray(g), np.asarray(g_none)), name

    # kept between the passes under the policy: per layer the (B, H, T, D)
    # output and two (B, H, T) statistics, nothing lane-broadcast
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss(keeping), q, k, v)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    assert kept.count(f"f32[{b},{h},{t}]") == 2 * layers
    assert kept.count(f"f32[{b},{h},{t},{d}]") == layers
    assert not any(shape.endswith((",128]", ",1]")) for shape in kept)


def test_fully_masked_row_zero_qk_grads(rng):
    """XLA-path parity: a fully padded sequence contributes no q/k gradient
    (masking is where-style, not a differentiable additive bias)."""
    b, t, s, h, d = 2, 4, 8, 1, 4
    q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
    pad_mask = jnp.zeros((b, s), bool).at[0].set(True)  # batch row 0 fully masked

    def loss(q, k, v):
        return jnp.sum(fused_attention(q, k, v, pad_mask, kv_block_size=8) ** 2)

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda q, k, v: jnp.sum(_xla(q, k, v, pad_mask) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq[0]), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dk[0]), 0.0, atol=1e-7)
    for g, gr in zip((dq, dk, dv), ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5)


def test_module_dispatch_parity(rng):
    """MultiHeadAttention(attn_impl='pallas') == attn_impl='xla' with the same
    params (the production dispatch path, reference ``model.py:66-74``)."""
    b, t, s = 2, 8, 24
    x_q = _rand(rng, b, t, 16)
    x_kv = _rand(rng, b, s, 12)
    pad_mask = jnp.asarray(rng.random((b, s)) < 0.2)

    mha_xla = MultiHeadAttention(num_q_channels=16, num_kv_channels=12, num_heads=4)
    mha_pallas = MultiHeadAttention(
        num_q_channels=16, num_kv_channels=12, num_heads=4, attn_impl="pallas"
    )
    params = mha_xla.init(jax.random.key(0), x_q, x_kv)["params"]
    out_xla = mha_xla.apply({"params": params}, x_q, x_kv, pad_mask=pad_mask)
    out_pallas = mha_pallas.apply({"params": params}, x_q, x_kv, pad_mask=pad_mask)
    np.testing.assert_allclose(
        np.asarray(out_pallas), np.asarray(out_xla), atol=1e-5
    )


@pytest.mark.parametrize("t,s,q_blk", [(16, 32, 4), (12, 32, 4), (7, 32, 3)])
def test_query_blocking_matches_xla(rng, t, s, q_blk):
    """Multi-query-block grid (t_blk < T), including the pad-then-slice path
    when T has no usable divisor (t=7, q_blk=3 → pads to 9)."""
    q = _rand(rng, 2, t, 2, 8)
    k = _rand(rng, 2, s, 2, 8)
    v = _rand(rng, 2, s, 2, 8)
    pad = jnp.asarray(rng.random((2, s)) < 0.2)
    out = fused_attention(q, k, v, pad, kv_block_size=16, q_block_size=q_blk)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_xla(q, k, v, pad)), atol=2e-5
    )


def test_auto_dispatch_threshold(rng, monkeypatch):
    """'auto' picks the fused kernel iff the KV stream is long (>= 4096),
    the heads are shallow, AND the backend is a real TPU (off-TPU the kernel
    would run in interpreter mode)."""
    import perceiver_io_tpu.ops.pallas_attention as pa
    from perceiver_io_tpu.ops import attention as attn_mod

    calls = []
    real = pa.fused_attention

    def spy(*args, **kwargs):
        calls.append(args[1].shape[1])
        kwargs["interpret"] = True  # test runs on CPU
        return real(*args, **kwargs)

    monkeypatch.setattr(pa, "fused_attention", spy)

    mha = MultiHeadAttention(num_q_channels=16, num_kv_channels=16, num_heads=2)
    assert mha.attn_impl == "auto"
    short = _rand(rng, 1, 8, 16)
    long_kv = _rand(rng, 1, attn_mod.AUTO_PALLAS_MIN_KV, 16)
    params = mha.init(jax.random.key(0), short, short)["params"]

    # off-TPU: always xla, even at long KV
    mha.apply({"params": params}, short, long_kv)
    assert calls == []

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    mha.apply({"params": params}, short, short)
    assert calls == []  # S=8 -> xla
    mha.apply({"params": params}, short, long_kv)
    assert calls == [attn_mod.AUTO_PALLAS_MIN_KV]


# -- sequence-parallel fused attention ---------------------------------------


class TestSeqParallelFusedAttention:
    """seq_parallel_fused_attention == fused_attention with KV sharded over
    the mesh: each device touches only its S/n slice, stats merge via
    pmax/psum, gradients flow through the shard_map'd custom VJP."""

    def _inputs(self, rng, B=2, T=16, S=96, H=2, D=8):
        q = jnp.asarray(rng.normal(0, 1, (B, T, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(0, 1, (B, S, H, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(0, 1, (B, S, H, D)).astype(np.float32))
        return q, k, v

    def test_forward_matches_single_device(self, rng):
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng)
        pad = jnp.zeros((2, 96), bool).at[0, -13:].set(True)
        ref = fused_attention(q, k, v, pad_mask=pad)

        mesh = make_mesh(dp=2, tp=1, sp=4)
        out = seq_parallel_fused_attention(
            q, k, v, pad_mask=pad, mesh=mesh, axis="seq"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_forward_with_batch_axis(self, rng):
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng)
        ref = fused_attention(q, k, v)
        mesh = make_mesh(dp=2, tp=1, sp=4)
        out = seq_parallel_fused_attention(
            q, k, v, mesh=mesh, axis="seq", batch_axis="data"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_fully_padded_shard(self, rng):
        """A shard whose keys are ALL padding must contribute nothing."""
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng)
        pad = jnp.zeros((2, 96), bool).at[:, -24:].set(True)  # last shard
        ref = fused_attention(q, k, v, pad_mask=pad)
        mesh = make_mesh(dp=2, tp=1, sp=4)
        out = seq_parallel_fused_attention(
            q, k, v, pad_mask=pad, mesh=mesh, axis="seq"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("dp,tp,sp,batch_axis,head_axis", [
        (1, 1, 8, None, None),
        # replicated non-seq axes of size > 1: the transpose convention
        # double-counted these before the round-2 fix (grads came back
        # exactly dp*tp times too large while the forward stayed correct)
        (2, 1, 4, None, None),
        (1, 2, 4, None, None),
        (2, 2, 2, "data", None),
        # head (tensor-parallel) sharding: each device keeps H/tp heads
        # inside the shard_map instead of all-gathering them
        (1, 2, 4, None, "model"),
        (2, 2, 2, "data", "model"),
    ])
    def test_gradients_match_single_device(self, rng, dp, tp, sp, batch_axis,
                                           head_axis):
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng, S=64)
        pad = jnp.zeros((2, 64), bool).at[1, -9:].set(True)
        mesh = make_mesh(dp=dp, tp=tp, sp=sp)

        def loss_ref(q, k, v):
            return jnp.sum(fused_attention(q, k, v, pad_mask=pad) ** 2)

        def loss_sp(q, k, v):
            return jnp.sum(
                seq_parallel_fused_attention(
                    q, k, v, pad_mask=pad, mesh=mesh, axis="seq",
                    batch_axis=batch_axis, head_axis=head_axis,
                ) ** 2
            )

        ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4)

    def test_head_sharded_forward_and_validation(self, rng):
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng)  # H=2
        pad = jnp.zeros((2, 96), bool).at[0, -13:].set(True)
        mesh = make_mesh(dp=2, tp=2, sp=2)
        ref = fused_attention(q, k, v, pad_mask=pad)
        out = seq_parallel_fused_attention(
            q, k, v, pad_mask=pad, mesh=mesh, axis="seq",
            batch_axis="data", head_axis="model",
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

        q3, k3, v3 = self._inputs(rng, H=3)  # 3 % 2 != 0
        with pytest.raises(ValueError, match="head count"):
            seq_parallel_fused_attention(
                q3, k3, v3, mesh=mesh, axis="seq", head_axis="model"
            )

    def test_under_jit_with_sharded_inputs(self, rng):
        """The intended deployment: jit + pre-sharded global arrays."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng)
        mesh = make_mesh(dp=2, tp=1, sp=4)
        ref = fused_attention(q, k, v)

        q_s = jax.device_put(q, NamedSharding(mesh, P("data")))
        k_s = jax.device_put(k, NamedSharding(mesh, P("data", "seq")))
        v_s = jax.device_put(v, NamedSharding(mesh, P("data", "seq")))
        fn = jax.jit(
            lambda q, k, v: seq_parallel_fused_attention(
                q, k, v, mesh=mesh, axis="seq", batch_axis="data"
            )
        )
        out = fn(q_s, k_s, v_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_uneven_kv_rejected(self, rng):
        from perceiver_io_tpu.parallel import make_mesh

        q, k, v = self._inputs(rng, S=90)  # 90 % 4 != 0
        mesh = make_mesh(dp=2, tp=1, sp=4)
        with pytest.raises(ValueError, match="divisible by the 'seq' mesh axis"):
            seq_parallel_fused_attention(q, k, v, mesh=mesh, axis="seq")


class TestRandomGeometryFuzz:
    """Seeded property fuzz over random (B, T, S, H, D) — VERDICT r4 item 8.

    Both resolution bugs on record (the 131k flash-CE row-divisor pathology
    and the awkward-S guard ordering, PERF.md r3) lived in block-RESOLUTION
    code yet were only ever caught by hardware measurement, because interpret
    mode resolves with alignment=1 and so never takes the divisor/padding/
    full-residency branches hardware takes. The `_TEST_ALIGNMENT` hook forces
    the compiled lane alignment while the kernel itself runs interpreted:
    every geometry here resolves its blocks exactly as on TPU, then checks
    numeric parity vs the XLA path, forward AND gradients.
    """

    N_GEOMETRIES = 60

    @staticmethod
    def _draw_dim(rng, lo, hi):
        """Bias toward resolution-interesting structure, not just uniforms:
        lane multiples, powers of two, 'awkward' odd-multiples (no aligned
        divisor above the unit), and plain uniforms."""
        mode = int(rng.integers(0, 4))
        if mode == 0:
            return int(rng.integers(lo, hi + 1))
        if mode == 1:  # lane multiple
            return 128 * int(rng.integers(max(1, lo // 128), max(2, hi // 128) + 1))
        if mode == 2:  # power of two
            cands = [x for x in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                                 2048, 4096) if lo <= x <= hi]
            return int(rng.choice(cands)) if cands else int(rng.integers(lo, hi + 1))
        # awkward: a small aligned factor times a prime-ish odd number
        primes = [7, 11, 13, 23, 31, 61, 127, 251]
        base = int(rng.choice([1, 2, 32, 128]))
        p = int(rng.choice(primes))
        val = base * p
        return int(min(max(val, lo), hi))

    @pytest.mark.slow  # fuzz sweep: the deterministic fwd/grad parity
    # cases above cover the guard boundaries in tier-1
    def test_fuzz_forward_and_grads_match_xla(self, lane_aligned):
        import perceiver_io_tpu.ops.pallas_attention as pa

        rng = np.random.default_rng(20260801)
        checked_branches = set()
        for case in range(self.N_GEOMETRIES):
            b = int(rng.integers(1, 3))
            h = int(rng.integers(1, 3))
            t = self._draw_dim(rng, 1, 640)
            s = self._draw_dim(rng, 1, 3100)
            d = int(rng.choice([16, 32, 64, 100, 128, 256]))
            q, k, v = (_rand(rng, b, n, h, d) for n in (t, s, s))
            pad = None
            if rng.integers(0, 2):
                pad = jnp.asarray(rng.integers(0, 2, (b, s)), bool)
                # keep at least one live key per example: a fully-masked row
                # has its own dedicated tests and NaN-free contract
                pad = pad.at[:, 0].set(False)

            # record which resolution branch this geometry lands in, so the
            # run provably covers them all (asserted below)
            s_blk = pa._kv_block_size(
                s, pa._auto_kv_block(s, d, t, 128, None), 128)
            checked_branches.add(
                ("divisor" if s_blk else
                 ("full" if s <= 4 * pa._auto_kv_block(s, d, t, 128, None)
                  else "padded"),
                 "tdiv" if pa._kv_block_size(t, pa.DEFAULT_Q_BLOCK, 128)
                 else ("tfull" if t <= 2 * pa.DEFAULT_Q_BLOCK else "tpad")))

            out = fused_attention(q, k, v, pad_mask=pad, interpret=True)
            ref = _xla(q, k, v, pad)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=5e-5,
                err_msg=f"fwd mismatch at case {case}: "
                        f"B{b} T{t} S{s} H{h} D{d} masked={pad is not None}")

            if case % 3 == 0:  # gradients on a third of the draws (cost)
                cot = _rand(rng, *out.shape)

                def loss_fused(q, k, v):
                    return jnp.sum(
                        fused_attention(q, k, v, pad_mask=pad, interpret=True)
                        * cot)

                def loss_xla(q, k, v):
                    return jnp.sum(_xla(q, k, v, pad) * cot)

                gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
                gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
                for name, a, bb in zip("qkv", gf, gx):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(bb), atol=1e-4,
                        err_msg=f"d{name} mismatch at case {case}: "
                                f"B{b} T{t} S{s} H{h} D{d}")
        # the fuzz is only worth its runtime if it actually visits the
        # branches hardware takes
        s_branches = {br[0] for br in checked_branches}
        t_branches = {br[1] for br in checked_branches}
        assert {"divisor", "full", "padded"} <= s_branches, s_branches
        assert {"tdiv", "tfull"} <= t_branches, t_branches

    def test_fuzz_resolution_invariants(self, lane_aligned):
        """Pure-resolution sweep (no kernel run — hundreds of geometries):
        every resolved block triple must be tiling-legal and free of the
        tiny-sequential-grid pathology by construction."""
        import perceiver_io_tpu.ops.pallas_attention as pa

        rng = np.random.default_rng(7)
        for _ in range(400):
            t = self._draw_dim(rng, 1, 4096)
            s = self._draw_dim(rng, 1, 200_000)
            d = int(rng.choice([16, 32, 64, 128, 256, 512]))
            explicit = rng.integers(0, 2)
            kv_req = int(rng.choice([256, 512, 1024, 2048])) if explicit else None
            q_req = int(rng.choice([256, 512, 1024])) if rng.integers(0, 2) else None

            # eval_shape: the resolution + padding decisions trace without
            # materializing the (up to 400 MB) zero arrays — this keeps the
            # 400-geometry sweep at seconds, not minutes
            q = jax.ShapeDtypeStruct((1, t, 1, d), jnp.float32)
            k = jax.ShapeDtypeStruct((1, s, 1, d), jnp.float32)
            bias = jax.ShapeDtypeStruct((1, s), jnp.float32)
            blks = {}

            def probe(q, k, v, bias):
                qq, kk, vv, bb, t_blk, s_blk, t_pad = pa._prepare_blocks(
                    q, k, v, bias, kv_req, q_req, interpret=True)
                blks.update(t_blk=t_blk, s_blk=s_blk, t_pad=t_pad)
                return qq, kk

            qq, kk = jax.eval_shape(probe, q, k, k, bias)
            t_blk, s_blk, t_pad = blks["t_blk"], blks["s_blk"], blks["t_pad"]
            s_total, t_total = kk.shape[2], qq.shape[2]
            # tiling legality: every block divides its (possibly padded) axis
            # and is lane-aligned unless it IS the full axis
            assert s_total % s_blk == 0 and t_total % t_blk == 0
            assert s_blk == s_total or s_blk % 128 == 0, (s, s_blk, s_total)
            assert t_blk == t_total or t_blk % 128 == 0, (t, t_blk, t_total)
            assert t_total == t + t_pad
            # no tiny-grid pathology: the sequential KV grid may not exceed
            # ~2x what the requested block implies (the 131k bug shape ran
            # 12,290 steps where ~77 were needed)
            req = kv_req or pa._auto_kv_block(s, d, t, 128, q_req)
            assert s_total // s_blk <= max(2 * -(-s // req), 1), (
                s, d, kv_req, s_blk, s_total)
            # the auto q-bump only inside its measured-safe envelope
            if q_req is None and t_blk > pa.DEFAULT_Q_BLOCK and t > 2 * pa.DEFAULT_Q_BLOCK:
                assert t_blk == pa.LONG_KV_Q_BLOCK
                assert s_blk * d <= pa.LONG_KV_SAFE_SBLK_D
                assert t_blk * s_blk <= pa.LONG_KV_SAFE_PROBS
                assert d <= pa.LONG_KV_MAX_D


class TestSeqParallelGeometryFuzz:
    """Random-geometry sweep for the SEQUENCE-PARALLEL kernel path
    (VERDICT r4 item 8 extended to the shard_map wrapper): shard-local
    S/n slices resolve their own blocks, and the pmax/psum statistic merge
    must agree with the single-device kernel — forward AND gradients — at
    lane-aligned resolution, for pad masks that straddle shard boundaries."""

    N_GEOMETRIES = 12

    @pytest.mark.slow  # fuzz sweep: tests/test_sharding.py::
    # test_pallas_sp_step_matches_xla_and_shards_kv stays tier-1
    def test_fuzz_sp_matches_single_device(self, lane_aligned):
        from perceiver_io_tpu.parallel import make_mesh

        mesh = make_mesh(dp=2, tp=1, sp=4)
        rng = np.random.default_rng(20260803)
        for case in range(self.N_GEOMETRIES):
            b = 2
            h = int(rng.integers(1, 3))
            t = int(rng.choice([8, 64, 129, 256]))
            # S must divide sp=4; sizes chosen so shard-local S/4 exercises
            # full-dim, divisor, and (at 6500/4=1625) the padding path
            s = int(rng.choice([128, 512, 1024, 4096, 6500]))
            d = int(rng.choice([16, 64, 128]))
            q = jnp.asarray(rng.normal(0, 1, (b, t, h, d)).astype(np.float32))
            k = jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
            v = jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
            pad = None
            if rng.integers(0, 2):
                pad = jnp.asarray(rng.integers(0, 2, (b, s)), bool)
                pad = pad.at[:, 0].set(False)

            ref = fused_attention(q, k, v, pad_mask=pad, interpret=True)
            out = seq_parallel_fused_attention(
                q, k, v, pad_mask=pad, mesh=mesh, axis="seq", interpret=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=5e-5,
                err_msg=f"sp fwd mismatch case {case}: B{b} T{t} S{s} H{h} D{d}")

            if case % 3 == 0:
                cot = jnp.asarray(
                    rng.normal(0, 1, ref.shape).astype(np.float32))

                def loss_sp(q, k, v):
                    return jnp.sum(seq_parallel_fused_attention(
                        q, k, v, pad_mask=pad, mesh=mesh, axis="seq",
                        interpret=True) * cot)

                def loss_ref(q, k, v):
                    return jnp.sum(fused_attention(
                        q, k, v, pad_mask=pad, interpret=True) * cot)

                gs = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
                gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
                for name, a, bb in zip("qkv", gs, gr):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(bb), atol=1e-4,
                        err_msg=f"sp d{name} mismatch case {case}: "
                                f"B{b} T{t} S{s} H{h} D{d}")
