"""Compile rehearsals for one TPU v5e chip: the main path's Pallas kernels
at real widths, compiled by the TPU compiler (interpret=False) for a
described, unattached v5e. Nothing runs; a kernel the chip's compiler
would refuse (tile alignment, unsupported casts, too much VMEM) fails
here at no chip time. The topology is described inside a fixture — never
at import — so that every xdist worker collects the same tests and only
the worker given this file loads the TPU library."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen1.5-0.5b serving widths: 16 query and 16 KV heads of 64, two KV
# heads a 128-lane pool row (`layers.kv_heads_per_row`), 16-token pages,
# 8 slots, bfloat16 caches; a 32-token prefill chunk; the kernels read
# one layer of pools stacked over N_LAYERS, at index LAYER
B, H, HD, PAGE, N_LP, CHUNK = 8, 16, 64, 16, 8, 32
P = 2
N_LAYERS = 3
LAYER = ((), jnp.int32)
BF16 = jnp.bfloat16
# (slots, pages a slot, chunk) of the smoke run above and of the
# qwen05b-serve-decode cell: 32 slots of 9 pages (prompts to 35 tokens,
# replies to 101), 64-token chunks
SERVE_SHAPES = {"smoke": (B, N_LP, CHUNK), "cell": (32, 9, 64)}


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2, with the
    persistent compile cache off (its entries for an unattached TPU
    cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - any failure means skip
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", list(SERVE_SHAPES))
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_attention_kernels_compile(one_chip, kind, shape):
    from repro.kernels.decode_attention.ops import gqa_decode_paged
    from repro.kernels.prefill_attention.ops import gqa_prefill_paged
    b, n_lp, chunk = SERVE_SHAPES[shape]
    pool = ((N_LAYERS, b * n_lp, H // P, PAGE, P * HD), BF16)
    tables = ((b, n_lp), jnp.int32)
    pos = ((b,), jnp.int32)
    if kind == "decode":
        _compile(lambda q, k, v, l, t, n: gqa_decode_paged(
            q, k, v, l, t, n, interpret=False),
            [((b, H, HD), BF16), pool, pool, LAYER, tables, pos], one_chip)
    else:
        _compile(lambda q, k, v, l, t, s: gqa_prefill_paged(
            q, k, v, l, t, s, interpret=False),
            [((b, chunk, H, HD), BF16), pool, pool, LAYER, tables, pos],
            one_chip)


def _paper_fl_rows() -> int:
    """Packed rows of ONE user's upload of the paper model (89,673
    parameters) — what the FL sync stacks per user."""
    from repro.core import wire as W
    from repro.models.api import param_specs
    from repro.nn import shapes_tree
    from repro.schemes.federated import CFG
    return W.plan_for(shapes_tree(param_specs(CFG))).n_rows


@pytest.mark.parametrize("rows", ["paper", 13])
@pytest.mark.parametrize("fused_mean", [False, True])
def test_packed_wire_kernels_compile(one_chip, rows, fused_mean):
    """The FL upload of 3 users through the packed wire: per-user rows of
    the paper model (a multiple of 8 by construction) and a row count
    that is not, which the kernel pads inside."""
    from repro.kernels.quant_channel.kernel import (packed_wire_2d,
                                                    packed_wire_mean_2d)
    n = 3
    r = _paper_fl_rows() if rows == "paper" else rows
    col = ((n * r, 1), jnp.float32)
    shapes = [((n * r, 256), jnp.float32), ((n * r, 256), jnp.uint32),
              col, col]
    if fused_mean:
        _compile(lambda b, rd, s, p, w: packed_wire_mean_2d(
            b, rd, s, p, w, 8, n, interpret=False), shapes + [col],
            one_chip)
    else:
        _compile(lambda b, rd, s, p: packed_wire_2d(
            b, rd, s, p, 8, interpret=False), shapes, one_chip)


# deepseek-v2-lite serving widths: 16 heads over one latent key head of
# 512 + 64, 16-token pages, the cell's 64 slots of 9 pages, a 64-token
# chunk; the held experts' grouped matmuls at d 2048 and width 1408 over
# 8 experts, for a decode step's 64 x 6 rows and a full chunk's
MLA_B, MLA_H, MLA_D, MLA_NLP = 64, 16, 576, 9


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_mla_paged_kernels_compile(one_chip, kind):
    from repro.kernels.decode_attention.ops import mla_decode_paged
    from repro.kernels.prefill_attention.ops import mla_prefill_paged
    pool = ((N_LAYERS, MLA_B * MLA_NLP, 1, PAGE, MLA_D), BF16)
    tables = ((MLA_B, MLA_NLP), jnp.int32)
    pos = ((MLA_B,), jnp.int32)
    if kind == "decode":
        c = _compile(lambda q, p, l, t, n: mla_decode_paged(
            q, p, l, t, n, scale=0.1147, interpret=False),
            [((MLA_B, MLA_H, MLA_D), BF16), pool, LAYER, tables, pos],
            one_chip)
    else:
        c = _compile(lambda q, p, l, t, s: mla_prefill_paged(
            q, p, l, t, s, scale=0.1147, interpret=False),
            [((MLA_B, 64, MLA_H, MLA_D), BF16), pool, LAYER, tables, pos],
            one_chip)
    assert f"mla_{kind}_paged" in c.as_text()


# a 32k context: 2048 pages of 16 a slot
LONG_NLP = 2048


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_paged_decode_kernels_compile_at_long_table(one_chip, kind):
    """The paged decode kernels at a 32k table still compile: the pages
    a block holds are bounded, so VMEM does not grow with the table."""
    from repro.kernels.decode_attention.ops import (gqa_decode_paged,
                                                    mla_decode_paged)
    tables = ((B, LONG_NLP), jnp.int32)
    pos = ((B,), jnp.int32)
    if kind == "gqa":
        pool = ((N_LAYERS, B * LONG_NLP, H // P, PAGE, P * HD), BF16)
        c = _compile(lambda q, k, v, l, t, n: gqa_decode_paged(
            q, k, v, l, t, n, interpret=False),
            [((B, H, HD), BF16), pool, pool, LAYER, tables, pos], one_chip)
    else:
        pool = ((N_LAYERS, B * LONG_NLP, 1, PAGE, MLA_D), BF16)
        c = _compile(lambda q, p, l, t, n: mla_decode_paged(
            q, p, l, t, n, scale=0.1147, interpret=False),
            [((B, MLA_H, MLA_D), BF16), pool, LAYER, tables, pos], one_chip)
    assert f"{kind}_decode_paged" in c.as_text()


@pytest.mark.parametrize("rows", [64 * 6, 4096 * 6])
@pytest.mark.parametrize("proj", ["gate_up", "down"])
def test_moe_gmm_kernel_compiles(one_chip, rows, proj):
    from repro.kernels.moe_gmm.ops import KERNEL_NAME, moe_gmm
    k, n = (2048, 1408) if proj == "gate_up" else (1408, 2048)
    c = _compile(lambda x, w, g: moe_gmm(x, w, g, 0, interpret=False),
                 [((rows, k), BF16), ((1, 8, k, n), BF16), ((8,), jnp.int32)],
                 one_chip)
    assert f"%{KERNEL_NAME}." in c.as_text()


# The serve steps of the benchmark's attention cells, whole (abstract
# weights, the kernel route), compiled for the chip: B's decode and
# 64-token prefill (32 slots x 9 pages of 16), deepseek-v2-lite's decode
# (64 slots x 9 pages, the 8 experts one chip of EP8 holds).
SERVE_PROGRAMS = {"qwen-decode": ("qwen1.5-0.5b", 32, "decode"),
                  "qwen-prefill": ("qwen1.5-0.5b", 32, "prefill"),
                  "dsv2lite-decode": ("deepseek-v2-lite", 64, "decode")}
# an array-valued HLO instruction: name, element type, dims, layout
_ARRAY = re.compile(r"%([\w.\-]+) = (\w+)\[([\d,]*)\]\{([^}]*)\} ([\w-]+)\(")


@pytest.fixture(scope="module")
def serve_programs(one_chip):
    """{program: (compiled HLO text, stacked pool shape, pool count)},
    each program compiled once, when a test first asks for it."""
    texts = {}

    def get(name):
        if name not in texts:
            texts[name] = _compile_serve_step(one_chip, *SERVE_PROGRAMS[name])
        return texts[name]

    return get


def _compile_serve_step(sharding, arch, slots, kind):
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.models.api import param_specs
    from repro.nn import shapes_tree
    from repro.runtime import serve_step as SS
    cfg = get_arch(arch)
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, experts_held=(0, 8),
                                  param_dtype=BF16)
    n_lp, page, chunk = 9, PAGE, 64
    n_pages = slots * n_lp
    sc = ShapeConfig("serve", n_lp * page, slots, "decode")

    def sds(shape, dtype, sharding=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          shapes_tree(param_specs(cfg)))
    cache = {k: sds(a.shape, a.dtype) for k, a in
             SS.paged_cache_specs(cfg, n_pages, page)[0].items()}
    i32 = jnp.int32
    if kind == "decode":
        step = SS.make_paged_decode_step(cfg, sc, page, kernel=True,
                                         stats=True)
        args = (params, cache, sds((slots, 1), i32), sds((slots,), i32),
                sds((slots, n_lp), i32), sds((slots,), jnp.bool_))
    else:
        step = SS.make_paged_prefill_step(cfg, sc, page, kernel=True,
                                          stats=True)
        args = (params, cache, sds((slots, chunk), i32), sds((slots,), i32),
                sds((slots,), i32), sds((slots, n_lp), i32))
    # the kernels resolve to interpret mode off a TPU; compile them for it
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(step).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    shape = next(iter(cache.values())).shape
    return text, shape, len(cache)


def _arrays(text):
    """(name, dims without unit dims, minor-to-major order of those dims,
    tiling and memory space, opcode) of each array-valued instruction."""
    out = []
    for name, _, dims, layout, op in _ARRAY.findall(text):
        dims = [int(d) for d in dims.split(",") if d]
        m2m, _, tiling = layout.partition(":")
        keep = [i for i, d in enumerate(dims) if d != 1]
        order = [keep.index(int(i)) for i in m2m.split(",")
                 if i and int(i) in keep]
        out.append((name, tuple(dims[i] for i in keep), tuple(order),
                    tiling, op))
    return out


def _squeeze(shape):
    return tuple(d for d in shape if d != 1)


@pytest.mark.parametrize("program", list(SERVE_PROGRAMS))
def test_serve_step_makes_no_layer_slice_of_the_pool(serve_programs,
                                                     program):
    """No instruction of the step outputs one layer of the pool, whatever
    its shape: the layer loop neither slices the pool nor writes a layer
    back, and nothing relays a layer out for the kernels or the write."""
    text, shape, _ = serve_programs(program)
    layer = math.prod(shape[1:])
    layer_sized = [(name, dims, op) for name, dims, _, _, op
                   in _arrays(text) if math.prod(dims) == layer]
    assert not layer_sized


@pytest.mark.parametrize("program", list(SERVE_PROGRAMS))
def test_serve_step_keeps_one_pool_layout(serve_programs, program):
    """The stacked pool has one layout from the program's entry through
    the layer loop to the kernels and out: row-major, the layout the
    paged kernels read."""
    text, shape, _ = serve_programs(program)
    layouts = {(order, tiling) for _, dims, order, tiling, _
               in _arrays(text) if dims == _squeeze(shape)}
    rank = len(_squeeze(shape))
    assert {order for order, _ in layouts} == {tuple(range(rank))[::-1]}
    assert len(layouts) == 1, layouts


@pytest.mark.parametrize("program", list(SERVE_PROGRAMS))
def test_serve_step_copies_each_pool_at_most_once(serve_programs, program):
    """At most one whole-pool copy a pool: the copy of the step's input,
    which the program does not donate."""
    text, shape, n_pools = serve_programs(program)
    copies = [name for name, dims, _, _, op in _arrays(text)
              if op in ("copy", "transpose") and
              math.prod(dims) == math.prod(shape)]
    assert len(copies) <= n_pools, copies
