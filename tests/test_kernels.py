"""Per-kernel allclose tests vs. the pure-jnp ref.py oracles, with
hypothesis shape/dtype/parameter sweeps (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.kernels.quant_channel.kernel import quant_channel_2d
from repro.kernels.quant_channel.ref import quant_channel_ref
from repro.kernels.quant_channel import ops as qc_ops
from repro.kernels.lstm_cell.kernel import lstm_final_state
from repro.kernels.lstm_cell.ref import lstm_final_state_ref
from repro.kernels.lstm_cell import ops as lstm_ops
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.conv_pool.ops import user_conv_pool
from repro.kernels.conv_pool.ref import conv_pool_ref
from repro.core import channel as CH

HS = settings(max_examples=12, deadline=None)


# ------------------------------------------------------------ quant_channel
@HS
@given(m=st.sampled_from([8, 128, 256]),
       n=st.sampled_from([128, 512, 1024]),
       bits=st.sampled_from([4, 8, 16]),
       p=st.floats(0.0, 0.2),
       seed=st.integers(0, 2 ** 16))
def test_quant_channel_matches_ref(m, n, bits, p, seed):
    key = jax.random.PRNGKey(seed)
    kx, kr = jax.random.split(key)
    x = jax.random.normal(kx, (m, n), jnp.float32)
    rand = jax.random.bits(kr, (m, n), jnp.uint32)
    pj = jnp.asarray([p], jnp.float32)
    out = quant_channel_2d(x, rand, pj, bits, interpret=True)
    ref = quant_channel_ref(x, rand, pj, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_channel_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (128, 512), dtype)
    rand = jax.random.bits(key, (128, 512), jnp.uint32)
    p = jnp.asarray([0.01], jnp.float32)
    out = quant_channel_2d(x.astype(jnp.float32), rand, p, 8)
    ref = quant_channel_ref(x.astype(jnp.float32), rand, p, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_quant_channel_noiseless_is_quantization():
    """p=0: the kernel must reduce to pure blockwise quantization with
    error bounded by scale/2 per element."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (128, 512), jnp.float32)
    rand = jax.random.bits(key, (128, 512), jnp.uint32)
    out = quant_channel_2d(x, rand, jnp.asarray([0.0], jnp.float32), 8)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert float(jnp.max(jnp.abs(out - x))) <= scale / 2 + 1e-6


def test_quant_channel_ops_arbitrary_shapes():
    """ops.transmit handles non-2D, non-block-multiple tensors."""
    key = jax.random.PRNGKey(2)
    for shape in [(7,), (3, 5, 11), (89_673,), (1, 1)]:
        x = jax.random.normal(jax.random.fold_in(key, hash(shape) % 97),
                              shape, jnp.float32)
        y = qc_ops.transmit(key, x, bits=8, snr_db=40.0, fading=False)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        # high SNR: almost no bit errors; output close to quantized input
        assert float(jnp.mean(jnp.abs(y - x))) < 0.05


@HS
@given(rows=st.sampled_from([8, 64, 120]), bits=st.sampled_from([4, 8]),
       seed=st.integers(0, 2 ** 16))
def test_packed_wire_kernel_matches_ref(rows, bits, seed):
    """packed_wire_2d (per-row scale/p tiles) == the jnp packed oracle."""
    from repro.kernels.quant_channel.kernel import packed_wire_2d
    from repro.kernels.quant_channel.ref import packed_wire_ref
    key = jax.random.PRNGKey(seed)
    kx, kr, ks, kp = jax.random.split(key, 4)
    x = jax.random.normal(kx, (rows, 256), jnp.float32)
    rand = jax.random.bits(kr, (rows, 256), jnp.uint32)
    scale = jax.random.uniform(ks, (rows, 1), jnp.float32, 0.01, 0.1)
    p = jax.random.uniform(kp, (rows, 1), jnp.float32, 0.0, 0.2)
    out = packed_wire_2d(x, rand, scale, p, bits, interpret=True)
    ref = packed_wire_ref(x, rand, scale, p, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quant_channel_ber_statistics():
    """Empirical flip rate of the kernel's hash-derived Bernoulli bits
    must track the requested p (validates the Murmur3 bit-plane trick)."""
    key = jax.random.PRNGKey(3)
    x = jnp.zeros((256, 512), jnp.float32)  # q=0 everywhere, code=qmax
    rand = jax.random.bits(key, (256, 512), jnp.uint32)
    p = 0.05
    out = quant_channel_2d(x, rand, jnp.asarray([p], jnp.float32), 8)
    # with x==0 the scale collapses to eps; instead count via nonzero out
    changed = float(jnp.mean((out != 0).astype(jnp.float32)))
    # P(any of 8 bits flips) = 1-(1-p)^8 ~ 0.337
    expect = 1 - (1 - p) ** 8
    assert abs(changed - expect) < 0.02


# ---------------------------------------------------------------- lstm_cell
@HS
@given(b=st.sampled_from([1, 4, 16]),
       t=st.sampled_from([1, 7, 14, 30]),
       h=st.sampled_from([8, 32]),
       seed=st.integers(0, 2 ** 16))
def test_lstm_kernel_matches_ref(b, t, h, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    xw = jax.random.normal(k1, (b, t, 4 * h), jnp.float32)
    wh = jax.random.normal(k2, (h, 4 * h), jnp.float32) * 0.1
    h_k, c_k = lstm_final_state(xw, wh, interpret=True)
    h_r, c_r = lstm_final_state_ref(xw, wh)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r),
                               rtol=2e-5, atol=2e-5)


def test_lstm_layer_matches_model():
    """ops.lstm_layer == models/lstm_tiny.lstm_scan on the real weights."""
    from repro.models import lstm_tiny
    from repro.nn import init_params
    params = init_params(jax.random.PRNGKey(0), lstm_tiny.model_specs())
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 14, 32), jnp.float32)
    h_kernel = lstm_ops.lstm_layer(x, params["lstm_wx"], params["lstm_wh"],
                                   params["lstm_b"])
    h_model = lstm_tiny.lstm_scan(params, x)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_model),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- decode_attention
# the dense [B, Hkv, S, hd] caches of these tests are scattered into layer
# LAYER of a shared page pool stacked over N_LAYERS layers
# (`_paged_from_dense`; the other layers hold NaN, so a read of them
# shows) and attended through the paged kernel, against the references
# on the dense cache
PAGE = 16
N_LAYERS, LAYER = 3, 1


def _gqa_decode_paged(q, k, v, length, **kw):
    kp, vp, tables = _paged_from_dense(k, v, PAGE)
    return da_ops.gqa_decode_paged(q, kp, vp, LAYER, tables, length, **kw)


@HS
@given(b=st.sampled_from([1, 2]),
       hkv=st.sampled_from([1, 2, 8]),
       g=st.sampled_from([1, 4, 8]),
       s=st.sampled_from([128, 256]),
       hd=st.sampled_from([64, 128]),
       seed=st.integers(0, 2 ** 16))
def test_decode_attention_matches_ref(b, hkv, g, s, hd, seed):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, kl = jax.random.split(key, 4)
    H = hkv * g
    q = jax.random.normal(kq, (b, H, hd), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    length = jax.random.randint(kl, (), 1, s)
    out = _gqa_decode_paged(q, k, v, length, interpret=True)
    ref = decode_attention_ref(q.reshape(b, hkv, g, hd), k, v, length)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(b, H, hd)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [0, 64, 128])
def test_decode_attention_sliding_window(window):
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    b, hkv, g, s, hd = 2, 2, 4, 256, 64
    q = jax.random.normal(kq, (b, hkv * g, hd), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    length = jnp.int32(200)
    out = _gqa_decode_paged(q, k, v, length, window=window)
    ref = decode_attention_ref(q.reshape(b, hkv, g, hd), k, v, length,
                               window=window)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(b, hkv * g, hd)),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- conv_pool
@HS
@given(b=st.sampled_from([1, 4, 8, 16]),
       t=st.sampled_from([10, 30, 64]),
       e=st.sampled_from([8, 16]),
       f=st.sampled_from([32, 64]),
       seed=st.integers(0, 2 ** 16))
def test_conv_pool_matches_ref(b, t, e, f, seed):
    key = jax.random.PRNGKey(seed)
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, t, e), jnp.float32)
    w = jax.random.normal(kw, (3, e, f), jnp.float32) * 0.2
    bias = jax.random.normal(kb, (f,), jnp.float32) * 0.1
    out = user_conv_pool(x, w, bias)
    ref = conv_pool_ref(x, w, bias)
    assert out.shape == (b, (t - 2) // 2, f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_conv_pool_matches_paper_user_forward():
    """Kernel == the model's user-side partition (minus embedding)."""
    from repro.models import lstm_tiny
    from repro.nn import init_params
    params = init_params(jax.random.PRNGKey(0), lstm_tiny.model_specs())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 30), 1, 10_000)
    x = jnp.take(params["embed"], tokens, axis=0)
    out = user_conv_pool(x, params["conv_w"], params["conv_b"])
    ref = lstm_tiny.user_forward(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_masks_future():
    """Tokens beyond `length` must not contribute: poisoning them with
    huge values leaves the output unchanged."""
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    b, hkv, g, s, hd = 1, 2, 2, 128, 64
    q = jax.random.normal(kq, (b, hkv * g, hd), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    length = jnp.int32(64)
    out1 = _gqa_decode_paged(q, k, v, length)
    k2 = k.at[:, :, 64:].set(1e9)
    v2 = v.at[:, :, 64:].set(-1e9)
    out2 = _gqa_decode_paged(q, k2, v2, length)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_per_slot_lengths():
    """The serving engine's actual batched call: every slot at its OWN
    depth, a [B] length vector. Kernel (interpret) == jnp ref == the
    same rows run one-at-a-time with scalar lengths."""
    from repro.models.layers import decode_attention_jnp
    key = jax.random.PRNGKey(17)
    kq, kk, kv = jax.random.split(key, 3)
    b, hkv, g, s, hd = 8, 2, 4, 128, 64          # 8 serving slots
    q = jax.random.normal(kq, (b, hkv * g, hd), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    lengths = jnp.array([1, 7, 16, 33, 64, 100, 127, 128], jnp.int32)
    kp, vp, tables = _paged_from_dense(k, v, PAGE)
    out = da_ops.gqa_decode_paged(q, kp, vp, LAYER, tables, lengths,
                                  interpret=True)
    ref = decode_attention_ref(q.reshape(b, hkv, g, hd), k, v, lengths)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(b, hkv * g, hd)),
                               rtol=2e-4, atol=2e-4)
    jref = decode_attention_jnp(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jref),
                               rtol=2e-4, atol=2e-4)
    # row independence: each slot's output equals its own scalar run
    for i in range(b):
        one = da_ops.gqa_decode_paged(q[i:i + 1], kp, vp, LAYER,
                                      tables[i:i + 1], lengths[i],
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(one), rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- TPU in-kernel RNG
def test_tpu_kernel_rng_flag_defaults_off():
    """The in-kernel pltpu PRNG path is a real-TPU-only optimization;
    the module flag ships OFF so every default path keeps the host
    rand-buffer stream (and its goldens) bit-for-bit."""
    from repro.kernels.quant_channel import kernel as K
    assert K.TPU_KERNEL_RNG is False


def test_tpu_kernel_rng_rejects_interpret_and_missing_seed():
    """rng_mode="tpu" needs the compiled TPU lowering (pltpu.prng_*
    does not exist in interpret mode) and an explicit seed tile."""
    from repro.kernels.quant_channel.kernel import packed_wire_2d
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 128), jnp.float32)
    rand = jax.random.bits(key, (8, 128), jnp.uint32)
    scale = jnp.ones((8, 1), jnp.float32)
    p = jnp.zeros((8, 1), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        packed_wire_2d(x, rand, scale, p, 8, interpret=True,
                       rng_mode="tpu",
                       seed=jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(ValueError, match="seed"):
        packed_wire_2d(x, rand, scale, p, 8, interpret=False,
                       rng_mode="tpu")


# -------------------------------------------------------- prefill_attention
def _prefill_fixture(seed, b=8, hkv=2, g=4, s=128, hd=64, c=16):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, c, hkv * g, hd), jnp.float32)
    kc = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    vc = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    # staggered engine starts: every slot prefills at its own depth
    start = jnp.array([0, 3, 16, 21, 40, 64, 96, 112], jnp.int32)[:b]
    return q, kc, vc, start


@pytest.mark.parametrize("c", [4, 8, 16, 32])
def test_prefill_kernel_matches_jnp_at_engine_buckets(c):
    """The serve engine's actual batched prefill call at every
    power-of-two chunk bucket: flash-prefill kernel (interpret) == the
    pure-jnp masked-softmax oracle, with per-slot staggered starts."""
    from repro.kernels.prefill_attention import ops as pf_ops
    from repro.models.layers import prefill_attention_jnp
    q, kc, vc, start = _prefill_fixture(31, c=c)
    kp, vp, tables = _paged_from_dense(kc, vc, PAGE)
    out = pf_ops.gqa_prefill_paged(q, kp, vp, LAYER, tables, start,
                                   interpret=True)
    ref = prefill_attention_jnp(q, kc, vc, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [0, 24])
def test_prefill_kernel_layout_matches_ref(window):
    """Kernel-layout entry point vs its own ref.py oracle, with and
    without the sliding window."""
    from repro.kernels.prefill_attention.kernel import \
        paged_prefill_attention
    from repro.kernels.prefill_attention.ref import prefill_attention_ref
    b, hkv, g, s, hd, c = 4, 2, 8, 128, 128, 8
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hkv, c * g, hd), jnp.float32)
    kc = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    vc = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    start = jnp.array([0, 5, 32, 77], jnp.int32)
    kp, vp, tables = _paged_from_dense(kc, vc, PAGE)
    out = paged_prefill_attention(q, kp, vp, LAYER, tables, start, g,
                                  window=window, interpret=True)
    ref = prefill_attention_ref(q, kc, vc, start, g, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_prefill_kernel_is_causal():
    """Chunk token i must see cache columns <= start+i ONLY: poisoning
    every column beyond each row's last chunk position — and the chunk's
    own future columns — leaves the output unchanged."""
    from repro.kernels.prefill_attention import ops as pf_ops
    q, kc, vc, start = _prefill_fixture(17, c=8)

    def gqa_prefill_paged(kc, vc):
        kp, vp, tables = _paged_from_dense(kc, vc, PAGE)
        return pf_ops.gqa_prefill_paged(q, kp, vp, LAYER, tables, start,
                                        interpret=True)

    out1 = gqa_prefill_paged(kc, vc)
    kc2, vc2 = np.asarray(kc).copy(), np.asarray(vc).copy()
    for b, st in enumerate(np.asarray(start)):
        kc2[b, :, st + 8:] = 1e9
        vc2[b, :, st + 8:] = -1e9
    out2 = gqa_prefill_paged(kc2, vc2)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)
    # and token 0 of each chunk only sees columns <= start: poisoning
    # column start+1 changes later tokens but never token 0
    kc3, vc3 = np.asarray(kc).copy(), np.asarray(vc).copy()
    for b, st in enumerate(np.asarray(start)):
        kc3[b, :, st + 1:] = 1e9
        vc3[b, :, st + 1:] = -1e9
    out3 = gqa_prefill_paged(kc3, vc3)
    np.testing.assert_allclose(np.asarray(out1)[:, 0],
                               np.asarray(out3)[:, 0],
                               rtol=1e-5, atol=1e-5)


def _paged_from_dense(kc, vc, page, heads_per_row=1):
    """Scatter a dense [B,Hkv,S,hd] cache into layer LAYER of a shared
    pool stacked over N_LAYERS layers, with a non-trivial (reversed per
    slot) page mapping; every other layer holds NaN. `heads_per_row` p
    packs p KV heads side by side into each pool row, as
    `layers.kv_heads_per_row` lays out the served pools: [N_LAYERS,
    n_pages, Hkv/p, page, p*hd]."""
    b, hkv, s, hd = kc.shape
    n_lp = s // page
    n_pages = b * n_lp
    kp = np.full((N_LAYERS, n_pages, hkv, page, hd), np.nan, np.float32)
    vp = np.full((N_LAYERS, n_pages, hkv, page, hd), np.nan, np.float32)
    tables = np.zeros((b, n_lp), np.int32)
    order = np.arange(n_pages).reshape(b, n_lp)[:, ::-1]
    for bi in range(b):
        for j in range(n_lp):
            pid = order[bi, j]
            tables[bi, j] = pid
            kp[LAYER, pid] = np.asarray(kc)[bi, :, j * page:(j + 1) * page]
            vp[LAYER, pid] = np.asarray(vc)[bi, :, j * page:(j + 1) * page]
    p = heads_per_row

    def pack(pool):
        pool = pool.reshape(N_LAYERS, n_pages, hkv // p, p, page, hd)
        return pool.transpose(0, 1, 2, 4, 3, 5).reshape(
            N_LAYERS, n_pages, hkv // p, page, p * hd)

    return jnp.asarray(pack(kp)), jnp.asarray(pack(vp)), jnp.asarray(tables)


@pytest.mark.parametrize("hkv,g,hd,page,lengths,window,kind,p", [
    pytest.param(2, 4, 64, 16, [1, 17, 40, 64], 0, "gqa", 1, id="grouped"),
    pytest.param(16, 1, 64, 16, [0, 1, 16, 32, 64], 0, "gqa", 1,
                 id="qwen-heads-edge-lengths"),
    pytest.param(2, 4, 64, 16, [0, 1, 16, 32, 64], 0, "gqa", 1,
                 id="grouped-edge-lengths"),
    pytest.param(2, 4, 64, 16, [1, 17, 40, 64], 20, "gqa", 1, id="window"),
    pytest.param(2, 4, 64, 4, [0, 1, 16, 79, 80], 0, "gqa", 1,
                 id="blocks-of-pages"),
    pytest.param(2, 4, 64, 4, [1, 17, 70, 80], 9, "gqa", 1,
                 id="blocks-of-pages-window"),
    pytest.param(1, 16, 576, 16, [0, 1, 16, 32, 64], 0, "mla", 1,
                 id="mla"),
    pytest.param(16, 1, 64, 16, [1, 16, 33, 64], 0, "nan", 1,
                 id="nan-pages-past-length"),
    pytest.param(16, 1, 64, 16, [0, 1, 16, 32, 64], 0, "gqa", 2,
                 id="qwen-heads-two-a-row"),
    pytest.param(2, 4, 64, 4, [1, 17, 70, 80], 9, "gqa", 2,
                 id="grouped-two-a-row-window"),
    pytest.param(4, 2, 32, 16, [1, 16, 33, 64], 0, "nan", 4,
                 id="nan-four-a-row"),
])
def test_paged_decode_kernel_matches_dense_jnp(hkv, g, hd, page, lengths,
                                               window, kind, p):
    """Paged flash decode through per-slot page tables == dense jnp
    attention over the gathered view, at per-slot lengths: 0, 1, page
    boundaries and the full table; a table of 20 pages of 4 spans more
    than one block of pages. A slot of length 0 has written nothing
    (its cache is zeros), so the reference's all-masked softmax is the
    zero vector the kernel returns. `nan`: every table entry past a
    slot's length points at a page of NaN, which the kernel must never
    read. `p` > 1 packs p KV heads into each pool row, as the served
    pools of 64-wide heads are."""
    from repro.models.layers import decode_attention_jnp, paged_view
    key = jax.random.PRNGKey(23)
    kq, kk, kv = jax.random.split(key, 3)
    b, s = len(lengths), max(lengths)
    lengths = jnp.array(lengths, jnp.int32)
    empty = (lengths == 0)[:, None, None, None]
    q = jax.random.normal(kq, (b, hkv * g, hd), jnp.float32)
    kc = jnp.where(empty, 0, jax.random.normal(kk, (b, hkv, s, hd)))
    vc = jnp.where(empty, 0, jax.random.normal(kv, (b, hkv, s, hd)))
    kp, vp, tables = _paged_from_dense(kc, vc, page, p)
    view_k = paged_view(kp, LAYER, tables, hd)
    view_v = paged_view(vp, LAYER, tables, hd)
    np.testing.assert_array_equal(np.asarray(view_k), np.asarray(kc))
    if kind == "mla":
        out = da_ops.mla_decode_paged(q, kp, LAYER, tables, lengths,
                                      scale=0.125, interpret=True)
        ref = decode_attention_jnp(q, view_k, view_k, lengths, scale=0.125)
    else:
        if kind == "nan":
            n_pages = kp.shape[1]
            nan = jnp.full(kp.shape[:1] + (1,) + kp.shape[2:], jnp.nan,
                           jnp.float32)
            past = (jnp.arange(tables.shape[1])[None]
                    >= (lengths[:, None] + page - 1) // page)
            tables = jnp.where(past, n_pages, tables)
            kp = jnp.concatenate([kp, nan], axis=1)
            vp = jnp.concatenate([vp, nan], axis=1)
        out = da_ops.gqa_decode_paged(q, kp, vp, LAYER, tables, lengths,
                                      window=window, interpret=True)
        ref = decode_attention_jnp(q, view_k, view_v, lengths, window=window)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_prefill_kernel_matches_dense_jnp():
    """Paged flash prefill through page tables == the dense jnp prefill
    oracle on the gathered view (staggered starts, chunk bucket 8)."""
    _check_paged_prefill(1)


def test_paged_prefill_kernel_reads_rows_of_two_heads():
    """As above over pools whose rows hold two KV heads side by side, as
    the served pools of 64-wide heads are (`layers.kv_heads_per_row`)."""
    _check_paged_prefill(2)


def _check_paged_prefill(p):
    from repro.kernels.prefill_attention import ops as pf_ops
    from repro.models.layers import paged_view, prefill_attention_jnp
    key = jax.random.PRNGKey(29)
    kq, kk, kv = jax.random.split(key, 3)
    b, hkv, g, s, hd, page, c = 4, 2, 4, 64, 64, 16, 8
    q = jax.random.normal(kq, (b, c, hkv * g, hd), jnp.float32)
    kc = jax.random.normal(kk, (b, hkv, s, hd), jnp.float32)
    vc = jax.random.normal(kv, (b, hkv, s, hd), jnp.float32)
    kp, vp, tables = _paged_from_dense(kc, vc, page, p)
    start = jnp.array([0, 9, 24, 50], jnp.int32)
    out = pf_ops.gqa_prefill_paged(q, kp, vp, LAYER, tables, start,
                                   interpret=True)
    ref = prefill_attention_jnp(q, paged_view(kp, LAYER, tables, hd),
                                paged_view(vp, LAYER, tables, hd), start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_insert_drops_masked_rows():
    """paged_insert writes each slot's C consecutive rows into one layer
    of the stacked pool through its page table and DROPS rows with
    keep=False — the write-site masking the batchless shared pool relies
    on (garbage from inactive slots must never land). Every other layer,
    and every masked or unwritten row, keeps its bytes."""
    from repro.models.layers import paged_insert, paged_view
    hkv, page, n_lp, b, c = 2, 4, 3, 2, 4
    before = jax.random.normal(jax.random.PRNGKey(5),
                               (N_LAYERS, b * n_lp, hkv, page, 8))
    tables = jnp.asarray(np.arange(b * n_lp).reshape(b, n_lp)[:, ::-1],
                         jnp.int32)
    start = jnp.asarray([0, 5], jnp.int32)     # columns 0-3 and 5-8
    vals = jax.random.normal(jax.random.PRNGKey(6), (b, c, hkv, 8))
    keep = jnp.asarray([[True, True, False, True],
                        [True, False, True, True]])
    out = paged_insert(before, LAYER, tables, start, vals, keep)
    for layer in range(N_LAYERS):
        if layer != LAYER:
            np.testing.assert_array_equal(np.asarray(out[layer]),
                                          np.asarray(before[layer]))
    view = np.asarray(paged_view(out, LAYER, tables))    # [B, Hkv, S, hd]
    old = np.asarray(paged_view(before, LAYER, tables))
    written = np.zeros((b, n_lp * page), bool)
    written[0, [0, 1, 3]] = True
    written[1, [5, 7, 8]] = True
    np.testing.assert_array_equal(view.transpose(0, 2, 1, 3)[~written],
                                  old.transpose(0, 2, 1, 3)[~written])
    for bi, cols in enumerate(([0, 1, 3], [5, 7, 8])):
        rows = [col - int(start[bi]) for col in cols]
        want = np.asarray(vals[bi, rows]).swapaxes(0, 1)   # [Hkv, n, hd]
        np.testing.assert_array_equal(view[bi][:, cols], want)


def test_paged_insert_packs_heads_into_rows():
    """Over a pool whose rows hold p KV heads side by side, paged_insert
    puts head i of a written row in lanes [i*hd, (i+1)*hd) of pool row
    (layer, page, head // p, offset), and paged_view reads them back as
    separate heads."""
    from repro.models.layers import kv_heads_per_row, paged_insert, paged_view
    hkv, hd, page, n_lp, b = 4, 32, 4, 2, 2
    p = kv_heads_per_row(hkv, hd)
    assert p == 4 and kv_heads_per_row(16, 64) == 2
    assert kv_heads_per_row(8, 128) == kv_heads_per_row(1, 64) == 1
    pool = jnp.zeros((N_LAYERS, b * n_lp, hkv // p, page, p * hd))
    tables = jnp.asarray([[3, 1], [0, 2]], jnp.int32)
    start = jnp.asarray([5, 2], jnp.int32)
    vals = jax.random.normal(jax.random.PRNGKey(7), (b, 1, hkv, hd))
    out = paged_insert(pool, LAYER, tables, start, vals,
                       jnp.ones((b, 1), bool))
    for bi in range(b):
        col = int(start[bi])
        row = np.asarray(out[LAYER, int(tables[bi, col // page]), 0,
                             col % page])
        np.testing.assert_array_equal(row.reshape(p, hd),
                                      np.asarray(vals[bi, 0]))
    view = np.asarray(paged_view(out, LAYER, tables, hd))   # [B,Hkv,S,hd]
    np.testing.assert_array_equal(view[[0, 1], :, [5, 2]],
                                  np.asarray(vals[:, 0]))


def _route_fixture(kind, step):
    """A reduced GQA (qwen) or latent-attention (deepseek) layer, its
    stacked pools of N_LAYERS layers filled with noise, per-slot page
    tables (reversed), and a decode step's or a prompt chunk's inputs:
    slot 2 inactive in decode, padded chunk tails in prefill."""
    import dataclasses
    from repro.configs import get_arch
    from repro.models import api as M
    from repro.models import transformer as T
    from repro.nn import init_params
    if kind == "gqa":
        cfg = dataclasses.replace(get_arch("qwen1.5-0.5b").reduced(),
                                  dtype=jnp.float32)
    else:
        cfg = dataclasses.replace(
            get_arch("deepseek-v2-lite"), n_layers=2, d_model=64, n_heads=8,
            d_ff=128, moe_d_ff=32, vocab_size=256, n_experts=8, top_k=2,
            kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), M.param_specs(cfg))
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    b, page, n_lp = 4, 4, 6
    shapes = T.paged_cache_shapes(cfg, b * n_lp, page)
    keys = jax.random.split(jax.random.PRNGKey(1), len(shapes))
    pools = {name: jax.random.normal(k, (N_LAYERS,) + shp[0][1:])
             for k, (name, shp) in zip(keys, shapes.items())}
    tables = jnp.asarray(np.arange(b * n_lp).reshape(b, n_lp)[:, ::-1],
                         jnp.int32)
    c = 1 if step == "decode" else 8
    x = jax.random.normal(jax.random.PRNGKey(2), (b, c, cfg.d_model))
    start = jnp.asarray([0, 3, 9, 13], jnp.int32)
    n_valid = jnp.asarray([c, c, 0, max(c - 3, 1)], jnp.int32)
    active = None if step == "prefill" else jnp.asarray(
        [True, True, False, True])
    return cfg, p, pools, tables, x, start, n_valid, active


def _route_step(kind, step, cfg, p, pools, layer, tables, x, start, n_valid,
                active, kernel):
    from repro.models import layers as L
    from repro.models import mla
    pages = {"tables": tables, "page_size": 4, "active": active,
             "kernel": kernel}
    if kind == "mla":
        if step == "decode":
            out, pool = mla.mla_decode_slots(p, x, cfg, pools["latent"],
                                             layer, start, pages, kernel)
        else:
            out, pool = mla.mla_prefill_slots(p, x, cfg, pools["latent"],
                                              layer, start, n_valid, pages,
                                              kernel)
        return out, {"latent": pool}
    if step == "decode":
        out, k, v = L.attention_decode_slots(p, x, cfg, pools["k"],
                                             pools["v"], layer, start,
                                             pages=pages)
    else:
        out, k, v = L.attention_prefill_slots(p, x, cfg, pools["k"],
                                              pools["v"], layer, start,
                                              n_valid, pages=pages)
    return out, {"k": k, "v": v}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_stacked_pool_kernel_route_matches_jnp(kind, step, layer):
    """A serve step's attention at layer `layer` of a pool stacked over 3
    layers: the paged kernels (interpret mode), which read the stack at
    the layer index, give the jnp route's outputs on that layer, and
    both routes leave the same pools — the step's rows written at that
    layer, every other layer and every masked row (an inactive slot's,
    a chunk's padded tail) byte-identical to before."""
    cfg, p, pools, tables, x, start, n_valid, active = _route_fixture(
        kind, step)
    args = (cfg, p, pools, layer, tables, x, start, n_valid, active)
    out_k, pools_k = _route_step(kind, step, *args, kernel=True)
    out_j, pools_j = _route_step(kind, step, *args, kernel=False)
    rows = np.asarray(n_valid if step == "prefill" else active)
    np.testing.assert_allclose(np.asarray(out_k)[rows > 0],
                               np.asarray(out_j)[rows > 0],
                               rtol=2e-4, atol=2e-4)
    page, C = 4, x.shape[1]
    for name, before in pools.items():
        new = np.asarray(pools_k[name])
        np.testing.assert_array_equal(new, np.asarray(pools_j[name]))
        old = np.asarray(before)
        for other in set(range(N_LAYERS)) - {layer}:
            np.testing.assert_array_equal(new[other], old[other])
        kept = np.zeros(new.shape[1:4], bool)      # [n_pages, Hkv, page]
        for b in range(x.shape[0]):
            n = int(n_valid[b]) if step == "prefill" else int(rows[b])
            for col in range(int(start[b]), int(start[b]) + min(n, C)):
                kept[int(tables[b, col // page]), :, col % page] = True
        np.testing.assert_array_equal(new[layer][~kept], old[layer][~kept])
        assert not np.array_equal(new[layer][kept], old[layer][kept])
