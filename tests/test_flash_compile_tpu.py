"""The flash and grouped-matmul kernels compile for the chip at real
widths, without the chip: the TPU's compiler is installed here and compiles for a described
v5e (interpret mode cannot see a tile Mosaic refuses, or a working set
over the scoped-VMEM limit: the default the forward, dq and dkv leave it
at, the stated one of the one-pass backward). The
topology is described inside a fixture, never at import: only the worker
that is handed this file loads the TPU's library. Keep every such test
in THIS file."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.kernels import (
    flash_attention, flash_tiles, gmm_tiles, grouped_matmul)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# the OLMoE cell's call; the float32 caller at the widest head the
# budget admits 1024-wide k tiles for; padding inside a large tile,
# non-causal; a head narrower than a lane row; a sequence whose dK / dV
# no longer stay in VMEM for a whole head (67 MiB counted): its backward
# is dq and dkv
SHAPES = [
    (4096, 16, 128, jnp.bfloat16, True),
    (4096, 4, 256, jnp.float32, True),
    (1000, 4, 64, jnp.bfloat16, False),
    (2176, 2, 32, jnp.float32, True),
    (16384, 2, 128, jnp.float32, True),
]


@pytest.mark.parametrize("t,h,d,dtype,causal", SHAPES)
def test_flash_chosen_tiles_compile_for_v5e(one_chip, t, h, d, dtype,
                                            causal):
    x = jax.ShapeDtypeStruct((1, t, h, d), dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal).astype(
            jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    bq, bk = flash_tiles(t, d, dtype)
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    t_pad = -(-t // max(bq, bk)) * max(bq, bk)
    fuses = pk.flash.bwd_fuses(t_pad, bq, bk, d, d, dtype)
    assert fuses is (t < 16384)
    # square tiles of 1,024 run a causal call's cut tiles by quarters,
    # and say so
    edge = pk.flash.cut_half(bq, bk, causal)
    assert edge == (512 if causal and bq == bk == 1024 else 0)
    for which, there in (("fwd", True), ("bwd", fuses), ("dq", not fuses),
                         ("dkv", not fuses)):
        # the kernel's name is the device op's name: what a trace shows
        name = "flash_%s_%s_q%d_k%d" % (which, operands, bq, bk)
        assert (name + ("_e%d" % edge if edge else "") in text) is there
        assert (name + "_e" in text) is (there and edge > 0)


# the MiMo-V2-Flash share cell's two calls: 8 query heads of 192 on one
# key/value head, values of 128; a window of 128 with a sink, and full
@pytest.mark.parametrize("window,sink", [(128, True), (0, False)])
def test_flash_window_and_grouped_heads_compile_for_v5e(one_chip, window,
                                                        sink):
    t, h, d, dv = 4096, 8, 192, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(q, k, v, s):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window,
            sink=s if sink else None).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3) if sink
                            else (0, 1, 2))).lower(
        shape(1, t, h, d), shape(1, t, 1, d), shape(1, t, 1, dv),
        shape(h, dtype=jnp.float32)).compile().as_text()
    bq, bk = flash_tiles(t, d, jnp.bfloat16, window)
    assert (bq, bk) == ((256, 256) if window else (1024, 1024))
    for which in ("fwd", "bwd"):
        # quarters of 128 are under the floor: the window kernels keep
        # their names; the full layers' tiles of 1,024 run quarters of 512
        assert re.search(r"flash_%s_bf16_q%d_k%d%s\b" % (
            which, bq, bk, "_w128" if window else "_e512"), text)
    assert "flash_dq_" not in text and "flash_dkv_" not in text


# the LM cells' attention calls (T, query heads, key/value heads, D, Dv,
# window): Kanana's latent attention, OLMoE's, MiMo's full and window
# layers, LFM2's (heads of 64: half a lane row a head, 4 query heads a
# key/value head), Falcon-H1's share (10 query heads on 2: five a
# key/value head), Trinity-Mini's window layers (a band of 2,048 keys on
# tiles of 1,024: three k tiles a q tile, one wholly inside) and its
# full layer, 8 query heads a key/value head in both
CELL_CALLS = {
    "trinity_window_8k": (8192, 32, 4, 128, 128, 2048),
    "trinity_full_8k": (8192, 32, 4, 128, 128, 0),
    "falcon_h1_4k": (4096, 10, 2, 128, 128, 0),
    "kanana2_8k": (8192, 32, 32, 192, 128, 0),
    "lfm2_8k": (8192, 32, 8, 64, 64, 0),
    "olmoe_4k": (4096, 16, 16, 128, 128, 0),
    "mimo_full_4k": (4096, 8, 1, 192, 128, 0),
    "mimo_window_4k": (4096, 8, 1, 192, 128, 128),
}


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_one_pass_backward_compiles_under_its_stated_limit(one_chip, cell):
    """The backward of each cell's call is the one kernel, and Mosaic
    compiles it under exactly the scoped VMEM ``_flash_vmem_bytes``
    counts for it (a working set over the limit fails the compile)."""
    t, h, g, d, dv, window = CELL_CALLS[cell]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, t, h, d), shape(1, t, g, d),
        shape(1, t, g, dv)).compile().as_text()
    bq, bk = flash_tiles(t, d, jnp.bfloat16, window)
    assert pk.flash.bwd_fuses(t, bq, bk, d, dv, jnp.bfloat16)
    edge = pk.flash.cut_half(bq, bk, True, window)
    assert edge == (0 if cell == "mimo_window_4k" else 512)
    name = "flash_bwd_bf16_q%d_k%d%s%s" % (
        bq, bk, "_w%d" % window if window else "",
        "_e%d" % edge if edge else "")
    calls = [line for line in text.splitlines()
             if name in line and "custom-call(" in line]
    assert len(calls) == 1
    assert "flash_dq_" not in text and "flash_dkv_" not in text
    limit, used = (
        int(re.search(r'"%s":\[\{"memory_space":"1","offset":"0",'
                      r'"size":"(\d+)"' % key, calls[0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert limit == pk.flash.flash_vmem_bytes(bq, bk, d, 2,
                                              resident=(t, d, dv))
    assert used <= limit <= pk.common.VMEM_RAISED_LIMIT


def test_the_latent_pair_compiles_at_the_kanana_cells_shape(one_chip):
    """``LatentAttention`` as the Kanana cell calls it (T 8,192, 32 heads
    of 128 + 64 query/key and 128 value dimensions from a latent of 512,
    bf16), forward and backward: the op takes the latent pair, Mosaic
    compiles the forward under the default scoped limit and the one-pass
    backward under exactly the VMEM ``_flash_vmem_bytes`` counts for it
    (the padded widths 256 / 128: what 192 / 128 counted), once each, and
    no single-key flash kernel is left in the program."""
    from mxnet_tpu.ops.transformer import latent_attention

    t, h, nope, rope, dv, latent = 8192, 32, 128, 64, 128, 512
    assert pk.latent_flash_takes(t, nope, rope, dv, jnp.bfloat16)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(latent_attention(
            *ins, num_heads=h, rope_dim=rope, v_head_dim=dv, theta=1e6,
            eps=1e-6, interleave=True).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(1, t, h * (nope + rope)), shape(1, t, latent + rope),
        shape(latent), shape(h * (nope + dv), latent)).compile().as_text()
    width = nope + 128                  # the rotary lanes to a lane row
    bq, bk = flash_tiles(t, width, jnp.bfloat16)
    assert (bq, bk) == (1024, 1024)
    calls = {which: [line for line in text.splitlines()
                     if "flash2_%s_bf16_q%d_k%d_e512" % (which, bq, bk) in line
                     and "custom-call(" in line]
             for which in ("fwd", "bwd")}
    assert [len(c) for c in calls.values()] == [1, 1]
    assert "flash_fwd_" not in text and "flash_bwd_" not in text
    # the pass over the query round the pair, each way
    for which in ("fwd", "bwd"):
        assert len(re.findall(r"(?m)^\s*%%latent_query_%s_bf16[.\d]* = "
                              % which, text)) == 1
    limit, used = (
        int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                      r'"size":"(\d+)"' % key, calls["bwd"][0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert limit == pk.flash.flash_vmem_bytes(bq, bk, width, 2,
                                              resident=(t, width, dv))
    assert used <= limit <= pk.common.VMEM_RAISED_LIMIT


def test_the_latent_pair_takes_the_call_that_rotates_nothing(one_chip):
    """``LatentAttention(rotary=False)`` at the Kimi Linear cell's shape
    (Kanana's signature on a hidden size of 2304: T 8,192, 32 heads of
    128 + 64 / 128 from a latent of 512): the same flash pair, once each
    way, and no pass of ``latent_query`` over the query (its 64 lanes are
    padded to a lane row by XLA: nothing is rotated)."""
    from mxnet_tpu.ops.transformer import latent_attention

    t, h, nope, rope, dv, latent = 8192, 32, 128, 64, 128, 512

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(latent_attention(
            *ins, num_heads=h, rope_dim=rope, v_head_dim=dv, theta=1e4,
            eps=1e-5, rotary=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(1, t, h * (nope + rope)), shape(1, t, latent + rope),
        shape(latent), shape(h * (nope + dv), latent)).compile().as_text()
    for which in ("fwd", "bwd"):
        assert len([line for line in text.splitlines()
                    if "flash2_%s_bf16_q1024_k1024_e512" % which in line
                    and "custom-call(" in line]) == 1
    assert "latent_query_" not in text
    assert "flash_fwd_" not in text and "flash_bwd_" not in text
    assert " cosine(" not in text and " sine(" not in text


@pytest.mark.parametrize("cell,t,heads,width,d", [
    ("keye_vl2", 8192, 16, 128, 2048), ("dots3", 4096, 64, 128, 1536)])
def test_the_indexers_choice_is_one_kernel_on_v5e(one_chip, cell, t, heads,
                                                  width, d):
    """``KeyIndexer`` as the two cells that have one call it (k 2,048 of
    8,192 / 4,096 keys): the choice is ONE ``topk_mask_*`` kernel at a
    block of 128 rows with the causal stop, inside the scoped default as
    it counts; no ``while`` (the bisection's 32 passes through HBM), no
    [T, T] array of 32-bit integers (its bits, its running count) and no
    pass of ``key_indexer``'s own over the mask."""
    from mxnet_tpu.ops.transformer import latent as latent_ops

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def index(x, q_w, k_w, gamma, beta, head_w):
        return latent_ops.key_indexer(
            x, x, q_w, k_w, gamma, beta, head_w, num_heads=heads,
            rope_dim=64, topk=2048, theta=1e6)

    text = jax.jit(index).lower(
        shape(1, t, d), shape(heads * width, d), shape(width, d),
        shape(width, dtype=jnp.float32), shape(width, dtype=jnp.float32),
        shape(heads, d)).compile().as_text()
    assert pk.top_k_rows((1, t, t), 2048) == 128
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    mine = [c for c in calls
            if "topk_mask_f32_r128_s%d_k2048_causal" % t in c]
    assert len(mine) == len(calls) == 1
    # the kernel alone: what it holds of VMEM is what it counts (in the
    # indexer's small program XLA lends the call's neighbours VMEM too)
    alone = jax.jit(lambda x: pk.top_k_mask(x, 2048, live=True, causal=True)
                    ).lower(shape(1, t, t, dtype=jnp.float32)
                            ).compile().as_text()
    used = int(re.search(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":'
        r'"\d+","size":"(\d+)"', [line for line in alone.splitlines()
                                 if "topk_mask_" in line
                                 and "custom-call(" in line][0]).group(1))
    assert used <= pk.topk.top_k_vmem_bytes(128, t) + 2 ** 20
    assert pk.topk.top_k_vmem_bytes(128, t) <= pk.common.VMEM_SCOPED_DEFAULT
    assert " while(" not in text
    assert not re.search(r"[su]32\[1,%d,%d\]" % (t, t), text)
    assert not [line for line in text.splitlines()
                if "fusion(" in line and "s8[1,%d,%d]" % (t, t) in line]


def test_the_selected_pair_compiles_at_the_keye_cells_shape(one_chip):
    """``Attention(keep=)`` as the Keye-VL-2.0 cell calls it (T 8,192,
    bf16, 32 query heads on 4 key/value heads of 128 under an int8
    keep-mask): the selected pair ``flashsel_*``, once each way, a q tile
    of 1,024 rows = 128 positions of each of a group's 8 heads against k
    tiles of 1,024 whose diagonal tile the backward runs by the column
    blocks of ``select_edge``, under the VMEM
    ``flash_vmem_bytes(select_rows=)`` counts (the keep-mask's tile at its
    own size: nothing of the group's), and no kernel of the unselected
    pair."""
    from mxnet_tpu.ops.transformer import attention as attention_ops

    t, h, g, d = 8192, 32, 4, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(q, k, v, keep):
        return jnp.sum(attention_ops._attention(
            dict(num_heads=h, num_kv_heads=g, causal=True, with_keep=True),
            [q, k, v, keep], True)[0].astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, t, h * d), shape(1, t, g * d), shape(1, t, g * d),
        shape(1, t, t, dtype=jnp.int8)).compile().as_text()
    assert pk.flash.select_tiles(t, h // g, d, d, jnp.bfloat16) == (
        128, 1024, t)
    assert pk.flash.select_edge("fwd", 128, 1024) == 0
    assert 128 <= pk.flash.select_edge("bwd", 128, 1024) < 1024
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    for which, resident in (("fwd", None), ("bwd", (t, d, d))):
        name = pk.flash._select_name(
            which, jnp.bfloat16, 1024, 1024, 8,
            pk.flash.select_edge(which, 128, 1024))
        assert name.startswith("flashsel_%s_bf16_q1024_k1024_g8" % which)
        mine = [c for c in calls if name + "/" in c]
        assert len(mine) == 1
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                          r'"size":"(\d+)"' % key, mine[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        count = pk.flash.flash_vmem_bytes(1024, 1024, d, 2,
                                          resident=resident, select_rows=128)
        assert limit == max(count, pk.common.VMEM_SCOPED_DEFAULT)
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT
    assert "flash_fwd_" not in text and "flash_bwd_" not in text
    assert "flash2" not in text


def test_the_selected_pair_compiles_at_16k_with_a_ranged_backward(one_chip):
    """``Attention(keep=)`` as the MiniCPM-SALA cell calls it (T 16,384,
    bf16, 16 query heads on ONE key/value head of 128): a q tile of 1,024
    rows = 64 positions of each of the 16 heads. A key/value head's dK / dV
    (33.5 MB in float32 scratch and bf16 blocks) do not fit VMEM beside a
    step, so the backward is the one that keeps them 8,192 keys at a time
    (``select_range``; ``flashsel_bwd_..._r8192``), under the VMEM its call
    states, and the one-pass backward is not in the program."""
    from mxnet_tpu.ops.transformer import attention as attention_ops

    t, h, g, d = 16384, 16, 1, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(q, k, v, keep):
        return jnp.sum(attention_ops._attention(
            dict(num_heads=h, num_kv_heads=g, causal=True, with_keep=True),
            [q, k, v, keep], True)[0].astype(jnp.float32))

    assert pk.flash_select_takes(t, h, g, d, d, jnp.bfloat16)
    assert pk.flash.select_tiles(t, h // g, d, d, jnp.bfloat16) == (
        64, 1024, t)
    assert not pk.flash.bwd_fuses(t, 1024, 1024, d, d, jnp.bfloat16,
                                  select_rows=64)
    assert pk.flash.select_range(t, 64, 16, 1024, d, d, jnp.bfloat16) == 8192
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, t, h * d), shape(1, t, g * d), shape(1, t, g * d),
        shape(1, t, t, dtype=jnp.int8)).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    counts = {"fwd": pk.flash.flash_vmem_bytes(1024, 1024, d, 2,
                                               select_rows=64),
              "bwd": pk.flash.select_range_vmem_bytes(8192, 64, 16, 1024, d,
                                                      d, 2)}
    for which, name in (("fwd", "flashsel_fwd_bf16_q1024_k1024_g16/"),
                        ("bwd", "flashsel_bwd_bf16_q1024_k1024_g16_e256"
                                "_r8192/")):
        mine = [c for c in calls if name in c]
        assert len(mine) == 1, name
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                          r'"size":"(\d+)"' % key, mine[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        assert limit == max(counts[which], pk.common.VMEM_SCOPED_DEFAULT)
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (used, limit)
    assert len([c for c in calls if "flashsel_" in c]) == 2
    assert "flash_fwd_" not in text and "flash_bwd_" not in text


@pytest.mark.parametrize("layer", ["full", "window"])
def test_the_dots3_cells_attention_compiles_for_v5e(one_chip, layer):
    """``LatentAttention`` as the dots3 cell calls it (T 4,096, bf16, a
    gate a head): a full layer's 16 heads of 128 + 64 / 128 from a latent
    of 512 under a keep-mask take the latent pair's SELECTED variant,
    once each way, under the VMEM ``flash_vmem_bytes`` and
    ``_keep_vmem_bytes`` count, and no
    kernel of the unselected pair; a window layer's 8 heads of 192 + 64 /
    128 from a latent of 1,024 (192 is not whole lane rows) take the
    single-key pair under a band of 513 keys on tiles of 1,024."""
    from mxnet_tpu.ops.transformer import latent_attention

    t = 4096
    h, nope, rope, dv, latent = ((16, 128, 64, 128, 512) if layer == "full"
                                 else (8, 192, 64, 128, 1024))

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(q, c, gamma, up, gate, *keep):
        return jnp.sum(latent_attention(
            q, c, gamma, up, num_heads=h, rope_dim=rope, v_head_dim=dv,
            theta=8e7, eps=1e-5, latent_scale=10 ** 0.5, gate=gate,
            window=0 if keep else 513,
            keep=keep[0] if keep else None).astype(jnp.float32))

    ins = [shape(1, t, h * (nope + rope)), shape(1, t, latent + rope),
           shape(latent), shape(h * (nope + dv), latent), shape(1, t, h)]
    if layer == "full":
        ins.append(shape(1, t, t, dtype=jnp.int8))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *ins).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    if layer == "window":
        assert pk.latent_flash_takes(t, nope, rope, dv, jnp.bfloat16) is False
        for which in ("fwd", "bwd"):
            assert len([c for c in calls if "flash_%s_bf16_q1024_k1024_w513"
                        % which in c]) == 1
        assert "flash2" not in text
        return
    assert pk.latent_flash_takes(t, nope, rope, dv, jnp.bfloat16)
    width = nope + 128
    for which, resident in (("fwd", None), ("bwd", (t, width, dv))):
        mine = [c for c in calls
                if "flash2sel_%s_bf16_q1024_k1024" % which in c]
        assert len(mine) == 1
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                          r'"size":"(\d+)"' % key, mine[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        assert limit == pk.flash.flash_vmem_bytes(
            1024, 1024, width, 2, resident=resident) \
            + pk.latent._keep_vmem_bytes(1024, 1024)
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT
    assert "flash2_fwd" not in text and "flash2_bwd" not in text
    assert "flash_fwd_" not in text and "flash_bwd_" not in text


def test_the_channel_delta_block_compiles_within_its_memory(one_chip):
    """``GatedDeltaNet`` in its channel form at the Kimi Linear cell's
    shape (T 8,192, 32 heads of 128 / 128, chunks of 64, bf16), value and
    gradients under ``remat`` as a training step runs it: the three
    convolutions are the taps' pair, the rule is the channel pair
    (``kda_fwd_`` / ``kda_bwd_bf16_c64_k128_v128_pre``, the unit norms and
    the decays made inside: Mosaic takes every slice, broadcast and
    product of both bodies, once each way, a step's working set under the
    scoped VMEM the calls state) and never the scalar pair, the
    sigmoid-gated norm is the gate and norm's pair in its token-major
    form (``gate_norm_fwd_`` / ``gate_norm_bwd_bf16_r256_g128_token_major_
    sigmoid``, once each way: it reads ``o`` straight from the rule's
    kernel's tuple and the rule's backward reads ``do`` straight from
    its, with no copy, slice or transpose between them), no [B, T, H, K]
    array is moved in front of the pair, and the compiled block's
    temporaries stay under 2.0 GB (1.48 before the norm's pair; the
    ``jax.numpy`` chunk form compiled to 4.02): the pair's residuals (the
    entering states 268 MB, the inverses 67, the tables 134) and what
    crosses between the scopes."""
    from mxnet_tpu.ops import transformer as tr
    from mxnet_tpu.ops.transformer import delta

    t, h, d = 8192, 32, 128
    assert pk.gdn_takes(h, d, d, 64, jnp.bfloat16, "channel")

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ins = [spec(1, t, h * d)] * 5 + [
        spec(1, t, h), spec(4, 3 * h * d), spec(h), spec(h * d), spec(d)]

    def op(q, k, v, gate, a, *rest):
        # the operands an elementwise neighbour's output, as a
        # projection's is in a step
        return tr.gated_delta_net(
            q * 2, k * 2, v * 2, gate * 2, a * 2, *rest, h, 64, 1e-5,
            allow_neg_eigval=False, remat=True, gate_act="sigmoid")

    delta._gated_delta_block.clear_cache()
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(op(*a).astype(jnp.float32)),
        argnums=tuple(range(len(ins))))).lower(*ins).compile()
    text = compiled.as_text()
    for which in ("fwd", "bwd"):
        # by the name a call is given, not by its operands': the rule's
        # pair reads the taps' outputs as they stand
        assert len([line for line in text.splitlines()
                    if "taps_%s_bf16_t512_c2048_k4_silu" % which
                    in line.split(" = ")[0]
                    and "custom-call(" in line]) == 3, which
        name = "kda_%s_bf16_c64_k128_v128_pre" % which
        calls = [line for line in text.splitlines()
                 if name in line.split(" = ")[0] and "custom-call(" in line]
        assert len(calls) == 1, name
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"0",'
                          r'"size":"(\d+)"' % key, calls[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (
            name, used, limit)
    assert "gdn_fwd_" not in text and "gdn_bwd_" not in text
    made_by = {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = \S+ ([\w\-]+)\(", text)}

    def operand_kinds(line):
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        return [made_by.get(o.split("%")[-1].strip())
                for o in operands.split(",")]

    for which in ("fwd", "bwd"):
        name = "gate_norm_%s_bf16_r256_g128_token_major_sigmoid" % which
        calls = [line for line in text.splitlines()
                 if name in line.split(" = ")[0] and "custom-call(" in line]
        assert len(calls) == 1, name
        kinds = operand_kinds(calls[0])
        assert not {"copy", "slice", "transpose", "reshape"} & set(kinds), (
            name, kinds)
        assert kinds[0] == "get-tuple-element", (name, kinds)
    rule_bwd, = [line for line in text.splitlines()
                 if "kda_bwd_" in line.split(" = ")[0]
                 and "custom-call(" in line]
    assert operand_kinds(rule_bwd)[-1] == "get-tuple-element"
    assert "triangular" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


# the OLMoE cell's two expert products (gate/up, down); a float32 caller
# at the widest result tile its budget admits; the MiMo share cell's two
# (8 held experts over a buffer of 2,048 rows); the Nemotron-3-Nano
# share cell's two (un-gated experts of 1,856 = 14.5 x 128 columns, which
# no multiple of 128 divides: every product holds the whole 1,856 in one
# block) at the rows 8 and 16 held experts expect and at their buffers
GMM_SHAPES = [
    (32768, 2048, 2048, 64, jnp.bfloat16),
    (32768, 1024, 2048, 64, jnp.bfloat16),
    (8192, 2048, 1024, 16, jnp.float32),
    (2048, 4096, 4096, 8, jnp.bfloat16),
    (2048, 2048, 4096, 8, jnp.bfloat16),
] + [(m, k, n, groups, jnp.bfloat16)
     for m, groups in ((3072, 8), (6144, 8), (6144, 16), (12288, 16))
     for k, n in ((2688, 1856), (1856, 2688))
] + [(m, k, n, 8, jnp.bfloat16)
     # the LFM2 share cell's: 8 held SwiGLU experts of 1536 (gate and up
     # one product of 3072 columns, then down; 2048 x 1536 the up
     # projection alone) at the rows they expect and at the buffer of
     # 8,192, whose row tile is 512: the dgrad of 3072 columns walks its
     # contraction in two steps and gives up half its result tile for it
     for m in (4096, 8192)
     for k, n in ((2048, 3072), (1536, 2048), (2048, 1536))
] + [(m, k, n, groups, jnp.bfloat16)
     # the Kimi Linear share cell's: SwiGLU experts of 1024 on a hidden
     # size of 2304 = 18 lane rows (gate and up one product of 2048
     # columns, then down) at the rows 8 and 16 held experts expect of
     # 8,192 x 8 pairs over 256 and at their buffers (twice the expected
     # rows, and the three times the 8-held cell took)
     for m, groups in ((2048, 8), (4096, 8), (6144, 8), (4096, 16),
                       (8192, 16))
     for k, n in ((2304, 2048), (1024, 2304))]


@pytest.mark.parametrize("m,k,n,groups,dtype", GMM_SHAPES)
def test_gmm_chosen_tiles_compile_for_v5e(one_chip, m, k, n, groups, dtype):
    lhs = jax.ShapeDtypeStruct((m, k), dtype, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((groups, k, n), dtype, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        # squared: the backward pass needs the forward's result
        return jnp.sum(
            grouped_matmul(lhs, rhs, sizes).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        lhs, rhs, sizes).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    tm, tk_d, tn_d = gmm_tiles(m, n, k, groups, dtype)
    for mode, tiles in (
            ("fwd", gmm_tiles(m, k, n, groups, dtype)),
            ("dgrad", (tm, tn_d, tk_d)),
            ("wgrad", gmm_tiles(m, k, n, groups, dtype, wgrad=True))):
        # the kernel's name is the device op's name: what a trace shows
        assert "gmm_%s_%s_m%d_k%d_n%d" % ((mode, operands) + tiles) in text
    # XLA's own grouped matmul is nowhere in the program
    assert "ragged_dot_tiling" not in text and "ragged-dot" not in text


# (held experts, d_model, columns of the up product, activation, rows):
# the Nemotron-3-Nano share cell's un-gated experts, whose up weight
# [16, 2688, 1856] the chip holds with 2,688 minor (1,856 is 14.5 lane
# rows); OLMoE's and the Kimi Linear share cell's SwiGLU experts, whole
# lane rows every way
EXPERT_STEPS = {
    "nemotron3_nano": (16, 2688, 1856, "relu2", 12288),
    "olmoe": (64, 2048, 2048, "swiglu", 32768),
    "kimi_linear": (8, 2304, 2048, "swiglu", 6144),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_STEPS))
def test_an_expert_layers_step_copies_no_weight_on_v5e(one_chip, cell):
    """A step shaped like a cell's over one expert layer's products
    (``parallel/moe.py::_experts`` on a share's path, its gradient, SGD
    with float32 momentum; both weights and both momenta donated entry
    parameters written back) as the TPU's compiler leaves it. Nemotron's
    up weight lies ``{1,2,0}`` in the entry layout, ``[16, 1856, 2688]``
    row-major, and so does its momentum; handed to the kernels as that
    (``held_transposed``), nothing the size of the weight is copied or
    transposed on the way in or out (the declared order costs four copies
    a block: PERF.md section 7), and the up product runs the three kernel
    signatures of the down product, roles permuted. The other cells'
    weights go as they are declared, under the names ``GMM_SHAPES``
    asserts."""
    from mxnet_tpu.parallel import moe

    experts, d, columns, activation, rows = EXPERT_STEPS[cell]
    hidden = columns if activation == "relu2" else columns // 2

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = {"w_gate_up": spec((experts, d, columns)),
         "w_down": spec((experts, hidden, d))}
    mom = {name: spec(v.shape, jnp.float32) for name, v in w.items()}

    def step(w, mom, x, counts, scale):
        def loss(w, x):
            return jnp.sum(moe._experts(w, x, counts, activation, scale)
                           .astype(jnp.float32) ** 2)

        grads, dx = jax.grad(loss, argnums=(0, 1))(w, x)
        mom = {name: 0.9 * mom[name] + grads[name].astype(jnp.float32)
               for name in w}
        w = {name: (w[name].astype(jnp.float32)
                    - 0.01 * mom[name]).astype(w[name].dtype) for name in w}
        return w, mom, dx

    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        w, mom, spec((rows, d)), spec((experts,), jnp.int32),
        spec((rows,), jnp.float32)).compile().as_text()
    calls = re.findall(r"= \S+ custom-call\(.*?op_name=\"[^\"]*?/"
                       r"(gmm_\w+)/pallas_call", text)
    bf16 = jnp.bfloat16
    tm = pk.gmm_row_tile(rows, experts)

    def names(k, n):
        """The three kernels over a weight declared (and held) [g, k, n]."""
        _, tn_d, tk_d = gmm_tiles(rows, n, k, experts, bf16)
        return sorted("gmm_%s_bf16_m%d_k%d_n%d" % ((mode,) + tiles)
                      for mode, tiles in (
                          ("fwd", gmm_tiles(rows, k, n, experts, bf16)),
                          ("dgrad", (tm, tk_d, tn_d)),
                          ("wgrad", gmm_tiles(rows, k, n, experts, bf16,
                                              wgrad=True))))

    moved = re.findall(r"= (\w+\[[\d,]+\])\S* (?:copy|transpose)\(", text)
    sized = [m for m in moved if str(columns) in m and str(d) in m
             and m.count(",") == 2]
    entry = text.splitlines()[0]
    if cell == "nemotron3_nano":
        assert pk.held_transposed((experts, d, columns))
        # the entry layout: both arrays twice (in, and the donated out)
        for held in ("bf16[16,2688,1856]{1,2,0:", "f32[16,2688,1856]{1,2,0:"):
            assert entry.count(held) == 2, held
        assert not sized, sized
        # the up product's three ARE the down product's
        assert sorted(calls) == sorted(2 * names(hidden, d))
        assert sorted(set(calls)) == [
            "gmm_dgrad_bf16_m256_k1856_n896", "gmm_fwd_bf16_m256_k1856_n896",
            "gmm_wgrad_bf16_m256_k1856_n384"]
    else:
        assert not pk.held_transposed((experts, d, columns))
        assert "{1,2,0:" not in entry
        assert sorted(calls) == sorted(names(d, columns) + names(hidden, d))
    assert "ragged_dot_tiling" not in text and "ragged-dot" not in text


def test_expert_layer_moves_rows_by_gathers_on_v5e(one_chip):
    """One OLMoE expert layer at the cell's shape (4,096 tokens of 2,048,
    top-8 of 64 experts of width 1,024, bf16), forward and backward, as
    the TPU's compiler leaves it: the row moves are gathers (XLA turned
    none back into a scatter), the only scatter is the transpose of the
    router's ``top_k`` over [tokens, experts], and the index vectors are
    int32."""
    import re

    from mxnet_tpu.parallel.moe import topk_moe

    tokens, d, experts, hidden, top_k = 4096, 2048, 64, 1024, 8

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    w = {"gate_w": spec(d, experts),
         "w_gate_up": spec(experts, d, 2 * hidden),
         "w_down": spec(experts, hidden, d)}

    def loss(w, x):
        return jnp.sum(topk_moe(w, x, top_k)[0].astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        w, spec(tokens, d)).compile().as_text()
    scattered = re.findall(r"= (\S+?)\{[^ ]*\} scatter\(", text)
    # (the compiler flattens it)
    assert scattered and set(scattered) <= {
        "f32[%d,%d]" % (tokens, experts), "f32[%d]" % (tokens * experts)}
    gathered = re.findall(r"= (\S+?)\{[^ ]*\} gather\(", text)
    assert gathered.count("bf16[%d,%d]" % (tokens * top_k, d)) == 4
    assert "s64[%d]" % (tokens * top_k) not in text


# the four share cells' sorted segment sums (rows of the buffer, tokens,
# width): a token's rows summed as the grouped matmul's wgrad over an
# exact 0 / 1 table of 256 tokens a group, forward (``combine``) and
# backward (``dispatch``'s transpose): Kanana, LFM2, Nemotron-3-Nano
# (2,688 = 21 lane rows: three n tiles of 896), MiMo; and the float32
# [buffer, top_k] tables that place the routing weights' cotangents
# (three bf16-exact pieces side by side, padded to a lane row)
SEGMENT_SHAPES = [
    (12288, 8192, 2048, jnp.bfloat16, (128, 256, 2048)),
    (8192, 8192, 2048, jnp.bfloat16, (128, 256, 2048)),
    (12288, 8192, 2688, jnp.bfloat16, (128, 256, 896)),
    (2048, 4096, 4096, jnp.bfloat16, (128, 256, 2048)),
    (12288, 8192, 6, jnp.float32, (128, 256, 128)),
    (8192, 8192, 4, jnp.float32, (128, 256, 128)),
    (2048, 4096, 8, jnp.float32, (128, 256, 128)),
]


@pytest.mark.parametrize("m,tokens,n,dtype,tiles", SEGMENT_SHAPES)
def test_sorted_segment_sum_compiles_for_v5e(one_chip, m, tokens, n, dtype,
                                             tiles):
    rows = jax.ShapeDtypeStruct((m, n), dtype, sharding=one_chip)
    segment = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda r, s: pk.sorted_segment_sum(r, s, tokens)).lower(
        rows, segment).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    assert "gmm_wgrad_%s_m%d_k%d_n%d" % ((operands,) + tiles) in text
    assert pk.gmm.gmm_vmem_bytes(
        *tiles, jnp.dtype(dtype).itemsize,
        wgrad=True) <= pk.common.VMEM_SCOPED_DEFAULT
    assert " scatter(" not in text


# the seven LM cells' embedding gradients (ids a step, the table's width,
# its rows; bf16): Falcon-H1 (32,640 rows are 127.5 groups of 256: the
# padded sums are sliced), Olmo-Hybrid, OLMoE (196.5 groups), MiMo-V2-Flash,
# Kanana-2, Nemotron-3-Nano (2,688 = 21 lane rows), LFM2
EMBED_SHAPES = [
    (4096, 5120, 32640, (128, 256, 1280)),
    (4096, 3840, 12544, (128, 256, 1920)),
    (4096, 2048, 50304, (128, 256, 2048)),
    (4096, 4096, 19072, (128, 256, 2048)),
    (8192, 2048, 16032, (128, 256, 2048)),
    (8192, 2688, 16384, (128, 256, 896)),
    (8192, 2048, 8192, (128, 256, 2048)),
]


@pytest.mark.parametrize("m,width,vocab,tiles", EMBED_SHAPES)
def test_the_embeddings_gradient_compiles_for_v5e(one_chip, m, width, vocab,
                                                  tiles):
    """``Embedding``'s backward rule at a cell's shape as the TPU's
    compiler leaves it: one sort, the segment product at the tiles
    ``gmm_tiles`` gives (within the scoped VMEM the call asks for), no
    scatter, the whole table's rows out."""
    from mxnet_tpu.ops import indexing

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grad(table, ids, cot):
        return jax.vjp(lambda w: indexing._lookup(w, ids, vocab),
                       table)[1](cot)[0]

    compiled = jax.jit(grad).lower(
        spec((vocab, width)), spec((m,), jnp.int32),
        spec((m, width))).compile()
    text = compiled.as_text()
    assert pk.gmm_tiles(m, pk.gmm.SEGMENT_TILE, width,
                        -(-vocab // pk.gmm.SEGMENT_TILE), jnp.bfloat16,
                        wgrad=True) == tiles
    assert "gmm_wgrad_bf16_m%d_k%d_n%d" % tiles in text
    assert pk.gmm.gmm_vmem_bytes(
        *tiles, 2, wgrad=True) <= pk.common.VMEM_SCOPED_DEFAULT
    assert " scatter(" not in text and text.count(" sort(") == 1
    assert "-> bf16[%d,%d]" % (vocab, width) in text


def test_a_share_layer_moves_rows_without_a_scatter_on_v5e(one_chip):
    """One share layer at the Kanana cell's shape (8,192 tokens of 2,048,
    top-6 of 128 sigmoid-routed experts, 16 held of width 768, a buffer
    of 12,288 rows, bf16), forward and backward, as the TPU's compiler
    leaves it: no scatter at all (the tree's formulation had two of
    [8192, 2048] and one of [49152]), the segment product twice, the
    weights' table once, four gathers of the buffer's rows, int32 index
    vectors."""
    from mxnet_tpu.parallel.moe import topk_moe

    tokens, d, experts, held, hidden, top_k, bound = (
        8192, 2048, 128, 16, 768, 6, 12288)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    w = {"gate_w": spec(d, experts),
         "w_gate_up": spec(held, d, 2 * hidden),
         "w_down": spec(held, hidden, d)}

    def loss(w, x):
        y, _ = topk_moe(w, x, top_k, norm_topk_prob=True, scoring="sigmoid",
                        share_rows_bound=bound, routed_scale=2.448)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        w, spec(tokens, d)).compile().as_text()
    assert " scatter(" not in text
    calls = re.findall(r"= \S+ custom-call\(.*?op_name=\"[^\"]*?/"
                       r"(gmm_wgrad_\w+)/pallas_call", text)
    assert calls.count("gmm_wgrad_bf16_m128_k256_n2048") == 2
    assert calls.count("gmm_wgrad_f32_m128_k256_n128") == 1
    gathered = re.findall(r"= (\S+?)\{[^ ]*\} gather\(", text)
    assert gathered.count("bf16[%d,%d]" % (bound, d)) == 4
    assert "s64[%d]" % bound not in text


# the Nemotron-3-Nano cell's scan (one sequence of 8,192 tokens, 64 heads
# of 64 on 8 groups, state 128, chunks of 128, bf16); the Falcon-H1
# share's (4,096 tokens, 16 heads of 128 in ONE group, state 256: a step
# is sixteen lane tiles and a [256, 2048] float32 state); a float32 caller
# whose heads are whole lane rows, in chunks of 256 over a ragged length;
# the MiniCPM-SALA share's linear core (16,384 tokens, 16 heads of 128 EACH
# its own group, state 128: ``LinearAttention``'s keys and queries)
SSD_SHAPES = [
    (8192, 64, 64, 8, 128, 128, jnp.bfloat16),
    (4096, 16, 128, 1, 256, 128, jnp.bfloat16),
    (16384, 16, 128, 16, 128, 128, jnp.bfloat16),
    (1000, 4, 128, 2, 256, 256, jnp.float32),
]


@pytest.mark.parametrize("t,heads,p,groups,n,chunk,dtype", SSD_SHAPES)
def test_ssd_scan_kernels_compile_for_v5e(one_chip, t, heads, p, groups, n,
                                          chunk, dtype):
    """The scan's forward and backward kernels at real widths: Mosaic
    takes every slice and product of both bodies and a step's working set
    is under the scoped VMEM the calls state."""
    assert pk.ssd_takes(heads, p, n, groups, chunk, dtype)

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(pk.ssd_scan(*ins, chunk))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        spec(1, t, heads, p), spec(1, t, groups, n), spec(1, t, groups, n),
        spec(1, t, heads, dtype=jnp.float32), spec(heads, dtype=jnp.float32),
        spec(heads, dtype=jnp.float32)).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    for which in ("fwd", "bwd"):
        name = "ssd_%s_%s_q%d_p%d_n%d" % (which, operands, chunk, p, n)
        calls = [line for line in text.splitlines()
                 if name in line and "custom-call(" in line]
        assert len(calls) == 1, name
        # the stated limit starts past what XLA itself keeps in VMEM
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                          r'"size":"(\d+)"' % key, calls[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (
            name, used, limit)


# the Phi-4-mini-flash cell's selective scan (one sequence of 4,096 tokens,
# 5,120 channels of state 16, bf16: ten tiles of 512 channels by 32 chunks
# of 128 tokens); a float32 caller of three tiles of 128 over a ragged
# length
SSCAN_SHAPES = [
    (4096, 5120, jnp.bfloat16),
    (1000, 384, jnp.float32),
]


@pytest.mark.parametrize("t,channels,dtype", SSCAN_SHAPES)
def test_selective_scan_kernels_compile_for_v5e(one_chip, t, channels, dtype):
    """The selective scan's forward and backward kernels at real widths:
    Mosaic takes every slice, lane compare and reduction of both bodies
    and a step's working set (a chunk's states among it) is under the
    scoped VMEM the calls state."""
    n = 16
    assert pk.sscan_takes(channels, n, dtype)
    chunk, width = pk.sscan.sscan_tiles(channels, n, dtype)

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(pk.selective_scan(*ins).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        spec(1, t, channels), spec(1, t, channels, dtype=jnp.float32),
        spec(1, t, n), spec(1, t, n), spec(channels, n, dtype=jnp.float32),
        spec(channels, dtype=jnp.float32)).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    for which in ("fwd", "bwd"):
        name = "sscan_%s_%s_q%d_w%d_n%d" % (which, operands, chunk, width, n)
        calls = [line for line in text.splitlines()
                 if name in line and "custom-call(" in line]
        assert len(calls) == 1, name
        limit, used = (
            int(re.search(r'"%s":\[\{"memory_space":"1","offset":"\d+",'
                          r'"size":"(\d+)"' % key, calls[0]).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (
            name, used, limit)


@pytest.mark.parametrize("window", [512, 0])
def test_differential_attentions_two_maps_compile_at_the_cells_shape(
        one_chip, window):
    """40 query heads on 20 key/value heads of 64 as 20 pairs on 10 groups:
    two flash calls with a value of 128 on a query of 64, under the window
    of 512 and full, forward and backward."""
    from mxnet_tpu.ops.transformer import diff_attention

    t, heads, kv, d = 4096, 40, 20, 64

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(diff_attention(
            *ins, num_heads=heads, num_kv_heads=kv, lambda_init=0.5,
            window=window).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=tuple(range(8)))).lower(
        spec(1, t, heads * d), spec(1, t, kv * d), spec(1, t, kv * d),
        *(spec(d) for _ in range(4)), spec(2 * d)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "flash_" in line]
    # two maps, each a forward and a one-pass backward
    assert len([c for c in calls if "flash_fwd_" in c]) == 2
    assert len([c for c in calls if "flash_bwd_" in c]) == 2, calls


# the Olmo-Hybrid cell's delta rule (one sequence of 4,096 tokens, 30
# heads with keys of 96 and values of 192: neither a whole number of lane
# rows; chunks of 64, bf16, ten heads a step, five pairs); a float32
# caller whose heads are whole lane rows, seven heads a step (three pairs
# and a head beside zeros, its outputs behind a ``pl.when``), chunks of
# 128 over a ragged length (a pair's tables two lane rows wide)
GDN_SHAPES = [
    (4096, 30, 96, 192, 64, jnp.bfloat16),
    (300, 7, 128, 128, 128, jnp.float32),
]


def _scoped_vmem(text, name):
    """(the limit a kernel's call states, what Mosaic says it uses) of the
    one custom call named ``name`` in a compiled program's text."""
    calls = [line for line in text.splitlines()
             if name in line.split(" = ")[0] and "custom-call(" in line]
    assert len(calls) == 1, name
    return tuple(
        int(re.search(r'"%s":\[\{"memory_space":"1","offset":"0",'
                      r'"size":"(\d+)"' % key, calls[0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))


@pytest.mark.parametrize("t,heads,dk,dv,chunk,dtype", GDN_SHAPES)
def test_gated_delta_rule_kernels_compile_for_v5e(one_chip, t, heads, dk, dv,
                                                  chunk, dtype):
    """The delta rule's forward and backward kernels at real widths:
    Mosaic takes every slice, broadcast and product of both bodies (the
    substitution's row and column reads, keys of 96 padded to a lane row
    in VMEM) and a step's working set is under the scoped VMEM the calls
    state."""
    assert pk.gdn_takes(heads, dk, dv, chunk, dtype)

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(pk.gated_delta_rule(*ins, chunk))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec(1, t, heads, dk), spec(1, t, heads, dk), spec(1, t, heads, dv),
        spec(1, t, heads, dtype=jnp.float32),
        spec(1, t, heads, dtype=jnp.float32)).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    stated = pk.gdn.gdn_vmem_bytes(chunk, pk.gdn.gdn_group(heads), dk, dv,
                                   jnp.dtype(dtype).itemsize)
    for which in ("fwd", "bwd"):
        name = "gdn_%s_%s_c%d_k%d_v%d" % (which, operands, chunk, dk, dv)
        limit, used = _scoped_vmem(text, name)
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (
            name, used, limit)
        assert used <= stated, (name, used, stated)


# the Kimi Linear cell's channel rule (8,192 tokens) and Solar-Open2's
# (4,096), both 32 heads of 128 / 128 in chunks of 64, bf16, four pairs a
# step, the prologue made inside; three float32 heads (a pair and a head
# beside zeros) without it
KDA_SHAPES = {
    "kimi_linear": (8192, 32, jnp.bfloat16, True),
    "solar_open2": (4096, 32, jnp.bfloat16, True),
    "three_heads": (200, 3, jnp.float32, False),
}


@pytest.mark.parametrize("cell", list(KDA_SHAPES))
def test_channel_delta_rule_kernels_compile_for_v5e(one_chip, cell):
    """The channel pair alone at the cells' shapes: Mosaic takes every
    slice, select and product of the two bodies a PAIR of heads wide (the
    tables side by side in whole lane rows, the substitution's two column
    reads a tile, the stacked rows against the block diagonals) and a
    step's working set is under what ``kda_vmem_bytes`` states."""
    t, heads, dtype, fused = KDA_SHAPES[cell]
    d, chunk = 128, 64
    assert pk.gdn_takes(heads, d, d, chunk, dtype, "channel")

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    wide = spec(1, t, heads * d)
    if fused:
        def loss(*ins):
            return jnp.sum(pk.channel_delta_net(*ins, chunk))
        ins = (wide,) * 4 + (spec(1, t, heads, dtype=f32),
                             spec(heads, dtype=f32),
                             spec(heads * d, dtype=f32))
    else:
        def loss(*ins):
            return jnp.sum(pk.gdn.channel_delta_rule(*ins, chunk))
        by_head = spec(1, t, heads, d)
        ins = (by_head,) * 3 + (spec(1, t, heads, d, dtype=f32),
                                spec(1, t, heads, dtype=f32))
    text = jax.jit(jax.grad(loss, argnums=tuple(range(len(ins))))).lower(
        *ins).compile().as_text()
    stated = pk.gdn.kda_vmem_bytes(chunk, pk.gdn.kda_group(heads), heads, d,
                                   d, jnp.dtype(dtype).itemsize)
    for which in ("fwd", "bwd"):
        name = "kda_%s_%s_c%d_k%d_v%d%s" % (
            which, {"bfloat16": "bf16", "float32": "f32"}[
                jnp.dtype(dtype).name], chunk, d, d, "_pre" * fused)
        limit, used = _scoped_vmem(text, name)
        assert used <= limit <= pk.common.VMEM_RAISED_LIMIT, (
            name, used, limit)
        assert used <= stated, (name, used, stated)


# the three cells' convolved arrays: Nemotron's Mamba-2 window (columns
# 4,096 to 10,240 of an in_proj output 10,304 wide: 80.5 lane rows),
# Olmo-Hybrid's query / key (2,880 columns, 22.5 lane rows, taken whole)
# and value, LFM2's B | C | x; the Falcon-H1 share's window (columns
# 2,048 to 4,608 of an in_proj output 4,624 wide, 36.125 lane rows: a
# column tile of four lane rows, a time tile of 1,024); a float32 caller
# with three taps
TAPS_SHAPES = {
    "falcon_h1_mamba2": ("bias_silu", 4, (1, 4096, 4624), 2048, 2560,
                         jnp.bfloat16),
    "nemotron_mamba2": ("bias_silu", 4, (1, 8192, 10304), 4096, 6144,
                        jnp.bfloat16),
    "olmo_hybrid_query_key": ("silu", 4, (1, 4096, 2880), 0, 2880,
                              jnp.bfloat16),
    "olmo_hybrid_value": ("silu", 4, (1, 4096, 5760), 0, 5760, jnp.bfloat16),
    "lfm2_short_conv": ("gates", 3, (1, 8192, 6144), 0, 2048, jnp.bfloat16),
    # Kimi Linear's KDA query, key and value: 4,096 columns each, a
    # projection's whole output
    "kimi_kda_qkv": ("silu", 4, (1, 8192, 4096), 0, 4096, jnp.bfloat16),
    "float32_taps3": ("silu", 3, (2, 512, 640), 0, 640, jnp.float32),
}


@pytest.mark.parametrize("site", sorted(TAPS_SHAPES))
def test_causal_taps_kernels_compile_for_v5e(one_chip, site):
    """The taps' forward and backward kernels at real widths: Mosaic takes
    the sublane rolls, the ragged last lane step of a width taken whole
    and the three thirds of one block, the window's offset is an index
    map's and no slice or copy of the operand, and a step's blocks fit
    the scoped VMEM the calls state."""
    form, taps, shape, offset, channels, dtype = TAPS_SHAPES[site]
    assert pk.taps_takes(channels, shape[1], taps, dtype, form, offset,
                         shape[2])

    def spec(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(src, w, *bias):
        # the operand an elementwise neighbour's output, as a projection's
        # is in a step (an entry parameter's default layout is not the
        # kernel's where the width is no multiple of 128)
        out = pk.causal_conv(src * 2, w, *bias, form=form, offset=offset,
                             channels=channels)
        return jnp.sum(out.astype(jnp.float32))

    ins = [spec(*shape), spec(taps, channels)] + (
        [spec(channels)] if form == "bias_silu" else [])
    # the value too: the backward keeps the op's inputs alone, so a program
    # of gradients only holds no forward kernel at all
    text = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(ins))))).lower(*ins).compile().as_text()
    operands = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    for which in ("fwd", "bwd"):
        calls = [line for line in text.splitlines()
                 if "taps_%s_%s_" % (which, operands) in line
                 and "custom-call(" in line]
        assert len(calls) == 1, (which, len(calls))
        assert "_k%d_%s" % (taps, form) in calls[0]
        # Mosaic refuses a body over the limit its call states; what the
        # line reports as used counts the arrays XLA itself keeps in VMEM
        limit = int(re.search(
            r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
            r'"size":"(\d+)"', calls[0]).group(1))
        assert limit <= pk.common.VMEM_RAISED_LIMIT, (which, limit)
        operand = re.search(r"custom-call\(%([\w.\-]+)", calls[0]).group(1)
        assert not operand.startswith(("copy.", "slice")), (which, operand)


# the gate and grouped norm behind the three state-space cells' cores, as
# the blocks call it: Nemotron's Mamba-2 (8 groups of 512 columns, the gate
# the first 4,096 of an in_proj output 10,304 wide), the Falcon-H1 share's
# (ONE group of 2,048 under the gate's multiplier, in_proj 4,624 wide) and
# Olmo-Hybrid's delta net (30 heads of 192, 1.5 lane rows: two a block,
# ``o`` head-major from the rule's kernel)
GATE_NORM_BLOCKS = {
    "nemotron_mamba2": ("gate_first", 8192, 256, 512,
                        dict(heads=64, p=64, n=128, groups=8)),
    "falcon_h1_mamba2": ("gate_first", 4096, 256, 2048,
                         dict(heads=16, p=128, n=256, groups=1,
                              multipliers=(0.7, 1.1, 0.9, 1.2, 0.8))),
    "olmo_hybrid_delta_net": ("norm_first", 4096, 1024, 192,
                              dict(heads=30, dk=96, dv=192)),
}


@pytest.mark.parametrize("site", sorted(GATE_NORM_BLOCKS))
def test_gate_norm_kernels_compile_inside_their_blocks_for_v5e(one_chip,
                                                               site):
    """A block's value and gradients at the cell's shape: Mosaic takes
    both bodies (the paired heads' lane shifts and masked sums among
    them), each runs once, what each call reads is what the projection or
    the core's kernel wrote (no copy, slice or transpose in front of
    either, and ``do`` goes to the rule's backward as written), and a
    step's blocks fit the scoped VMEM the calls state."""
    from mxnet_tpu.ops import transformer as tr
    from mxnet_tpu.ops.transformer import delta, ssm

    form, t, rows, width, dims = GATE_NORM_BLOCKS[site]
    f32 = jnp.float32

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if form == "gate_first":
        h, p, n, g = (dims[k] for k in ("heads", "p", "n", "groups"))
        conv = h * p + 2 * g * n
        ins = [spec(1, t, 2 * h * p + 2 * g * n + h), spec(4, conv),
               spec(conv), spec(h), spec(h), spec(h), spec(h * p)]

        def op(proj, *rest):
            # the operand an elementwise neighbour's output, as a
            # projection's is in a step
            return tr.mamba2(proj * 2, *rest, h, p, n, g, 128, 1e-5,
                             remat=True,
                             multipliers=dims.get("multipliers"))
    else:
        h, dk, dv = (dims[k] for k in ("heads", "dk", "dv"))
        ins = [spec(1, t, h * dk), spec(1, t, h * dk), spec(1, t, h * dv),
               spec(1, t, h * dv), spec(1, t, h), spec(1, t, h),
               spec(4, 2 * h * dk + h * dv), spec(h), spec(h), spec(dv)]

        def op(q, k, v, gate, *rest):
            return tr.gated_delta_net(q * 2, k * 2, v * 2, gate * 2, *rest,
                                      h, 64, 1e-6, remat=True)

    ssm._mamba2_block.clear_cache()
    delta._gated_delta_block.clear_cache()
    text = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(op(*a).astype(f32)),
        argnums=tuple(range(len(ins))))).lower(*ins).compile().as_text()
    made_by = {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = \S+ ([\w\-]+)\(", text)}
    core = {"gate_first": "ssd_", "norm_first": "gdn_"}[form]
    for which in ("fwd", "bwd"):
        name = "gate_norm_%s_bf16_r%d_g%d_%s" % (which, rows, width, form)
        calls = [line for line in text.splitlines()
                 if name in line and "custom-call(" in line]
        assert len(calls) == 1, (name, len(calls))
        limit = int(re.search(
            r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
            r'"size":"(\d+)"', calls[0]).group(1))
        assert limit <= pk.common.VMEM_RAISED_LIMIT, (name, limit)
        operands = re.search(r"custom-call\(([^)]*)\)", calls[0]).group(1)
        kinds = [made_by.get(o.strip().lstrip("%"))
                 for o in operands.split(",")]
        # (a ``copy-done`` is XLA's prefetch of an operand into VMEM, not
        # a pass that writes HBM)
        assert not {"copy", "slice", "transpose"} & set(kinds), (name, kinds)
        # the core's output straight from its kernel's tuple
        assert kinds[0] == "get-tuple-element", (name, kinds)
    # the core's backward reads the cotangent where this one wrote it
    bwd = [line for line in text.splitlines()
           if core + "bwd_" in line and "custom-call(" in line]
    assert len(bwd) == 1
    last = re.search(r"custom-call\(([^)]*)\)", bwd[0]).group(1).split(",")[-1]
    assert made_by[last.strip().lstrip("%")] == "get-tuple-element"


# a stacked expert weight of the OLMoE cell's kind (three axes), an
# attention projection, a router: what a fused fit draws on the mesh
DRAWS = [((8, 1024, 2048), jnp.bfloat16), ((2048, 2048), jnp.bfloat16),
         ((2048, 64), jnp.float32)]


@pytest.mark.parametrize("shape,dtype", DRAWS)
def test_a_parameter_is_drawn_on_the_chip_in_one_fusion(one_chip, shape,
                                                        dtype):
    """``ndarray._draw_program`` for the TPU: no barrier (the host's
    bit-for-bit promise is the host's), so bits, normal, scale and cast
    are one pass with no float32 temporary, and the reshape from rows
    to the parameter's shape moves nothing."""
    import numpy as np

    from mxnet_tpu import ndarray as nd

    compiled = nd._draw_program(shape, np.dtype(dtype), one_chip).lower(
        jax.ShapeDtypeStruct((2,), np.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((), np.float32, sharding=one_chip)).compile()
    nd._draw_program.cache_clear()
    text = compiled.as_text()
    assert "opt-barrier" not in text
    out = compiled.memory_analysis()
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    assert out.output_size_in_bytes == nbytes
    assert out.temp_size_in_bytes < 1 << 20
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r" copy\(", entry)) <= 1     # the scalar sigma


# the OLMoE cell's head; Falcon-H1's, whose float32 logits pass a fixed
# scalar (``lm_head_cast`` -> ``lm_head_f32``) before the loss reads them
HEADS = {"olmoe": (4096, 2048, 50304, 1.0),
         "falcon_h1": (4096, 5120, 32640, 0.0078125)}


@pytest.mark.parametrize("cell", sorted(HEADS))
def test_the_head_and_its_loss_write_no_float32_table_on_v5e(one_chip, cell):
    """``lm_blocks.head_and_loss`` forward and backward at a cell's
    shape: the head's bf16 product is the only [tokens, vocab] array an
    op of the compiled step writes (``pick_log_softmax`` keeps it, the
    labels and a number a row; the gradient products make the cotangent
    inside their operand fusions), nothing is gathered or scattered, and
    the temporaries are that one array."""
    import mxnet_tpu as mx
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.models import lm_blocks

    tokens, hidden, vocab, logit_scale = HEADS[cell]
    sym = lm_blocks.head_and_loss(
        mx.sym.Variable("data"), mx.sym.Variable("softmax_label"), [],
        vocab, tokens, 1e-5, logit_scale=logit_scale)
    program = _GraphProgram(sym)

    def loss(params, label):
        outs, _ = program(dict(params, softmax_label=label), {},
                          jax.random.PRNGKey(0), True)
        return jnp.sum(outs[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"data": spec((tokens, hidden), jnp.bfloat16),
              "final_norm_gamma": spec((hidden,), jnp.bfloat16),
              "lm_head_weight": spec((vocab, hidden), jnp.bfloat16)}
    compiled = jax.jit(jax.grad(loss)).lower(
        params, spec((1, tokens), jnp.float32)).compile()
    text = compiled.as_text()
    entry = re.sub(r"\{[^{}]*\}", "", text[text.index("ENTRY"):])
    # what each op of the entry computation writes: the text between
    # ``=`` and the opcode, a type or a tuple of types
    written = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (.*?) [\w\-]+\(", entry, re.M)]
    table = "[%d,%d]" % (tokens, vocab)
    assert any("bf16" + table in w for w in written)
    assert not [w for w in written if "f32" + table in w]
    assert not re.search(r" (scatter|gather)\(", text)
    logits = tokens * vocab * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * logits


# two of ResNet-50's convolutions at the cell's batch of 256 in bf16: a
# 3 x 3 of stage 2 and the strided 1 x 1 ``stage2_unit1_sc``
# (data, filter, stride, pad)
RESNET_CONVS = {
    "stage2_unit2_conv2": ((256, 128, 28, 28), (128, 128, 3, 3), (1, 1),
                           (1, 1)),
    "stage2_unit1_sc": ((256, 256, 56, 56), (512, 256, 1, 1), (2, 2), (0, 0)),
}


@pytest.mark.parametrize("node", sorted(RESNET_CONVS))
def test_named_conv_gradients_compile_to_the_parents_program_on_v5e(
        one_chip, node, monkeypatch):
    """``ops/nn.py::_conv_named_grads`` is names and nothing else where
    it counts: for the chip the optimized HLO of a ``Convolution`` node's
    backward, its scopes ``dgrad`` and ``wgrad`` in it, is the bare
    differentiated call's once the metadata is stripped."""
    import mxnet_tpu as mx
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.ops import nn
    from test_conv_pass_scopes import PARENT_FORM, stripped

    dshape, wshape, stride, pad = RESNET_CONVS[node]
    program = _GraphProgram(mx.sym.Convolution(
        mx.sym.Variable("data"), kernel=wshape[2:], stride=stride, pad=pad,
        num_filter=wshape[0], no_bias=True, name=node))

    def loss(args):
        out, = program(args, {}, None, True)[0]
        return jnp.sum((out * out).astype(jnp.float32))

    args = {"data": jax.ShapeDtypeStruct(dshape, jnp.bfloat16,
                                         sharding=one_chip),
            node + "_weight": jax.ShapeDtypeStruct(wshape, jnp.bfloat16,
                                                   sharding=one_chip)}
    new = jax.jit(jax.grad(loss)).lower(args).compile().as_text()
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss)).lower(args).compile().as_text()
    # (alone, the filter gradient's convolution comes out of the TPU's
    # passes with no metadata at all, in either form; inside a whole step
    # it keeps its name: PERF.md section 5 item 2)
    assert "transpose(jvp(conv/%s))/dgrad/" % node in new
    assert "dgrad" not in old and "wgrad" not in old
    assert stripped(new) == stripped(old)


def test_the_stems_beta_takes_its_gradient_from_a_forward_on_v5e(one_chip):
    """ResNet-50's stem at the cell's size (``bn_data`` -> ``cast_in`` ->
    ``conv0``, batch 256), the batch not differentiated: for the chip no
    convolution writes the image's gradient, whole or summed; the one under
    ``dgrad`` is the float32 forward response to the three channels'
    indicator images, which meets the batch's summed cotangent (PR 72;
    tests/test_conv_shift_grad.py has the numbers)."""
    import mxnet_tpu as mx
    from mxnet_tpu.executor import _GraphProgram

    body = mx.sym.BatchNorm(mx.sym.Variable("data"), fix_gamma=True,
                            eps=2e-5, name="bn_data")
    body = mx.sym.Cast(body, dtype="bfloat16", name="cast_in")
    program = _GraphProgram(mx.sym.Convolution(
        body, kernel=(7, 7), stride=(2, 2), pad=(3, 3), num_filter=64,
        no_bias=True, name="conv0"))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    batch = sds((256, 3, 224, 224), jnp.float32)
    stats = {"bn_data_moving_mean": sds((3,), jnp.float32),
             "bn_data_moving_var": sds((3,), jnp.float32)}
    params = {"bn_data_gamma": sds((3,), jnp.float32),
              "bn_data_beta": sds((3,), jnp.float32),
              "conv0_weight": sds((64, 3, 7, 7), jnp.bfloat16)}

    def loss(p, data, aux):
        out, = program(dict(p, data=data), aux, None, True)[0]
        return jnp.sum((out * out).astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, batch, stats).compile(
        ).as_text()
    convs = re.findall(r"= (\w+)\[([\d,]+)\]\S* convolution\(([^\n]*)", text)
    assert len(convs) == 3, convs
    (dtype, dims), = [(dtype, dims) for dtype, dims, rest in convs
                      if "/dgrad/" in rest]
    # (the TPU's passes fold the map's rows into the batch: 112,24,15,64)
    assert dtype == "f32" and not dims.startswith("256,"), convs
    assert not [dims for _, dims, _ in convs if "224" in dims.split(",")]


# -- the rotation between its neighbours (PR 67) -----------------------------

# tokens, query heads, key/value heads: Ouro's and OLMoE's layer,
# Trinity-Mini's, Falcon-H1's (hidden 2,048 in front of all three)
ROPE_LAYERS = {
    "ouro_olmoe": (4096, 16, 16),
    "trinity_mini": (8192, 32, 4),
    "falcon_h1": (4096, 10, 2),
}


@pytest.mark.parametrize("cell", sorted(ROPE_LAYERS))
def test_the_rotation_is_two_kernels_and_moves_nothing_on_v5e(one_chip, cell):
    """Projection, ``rope``, ``attention``, forward and backward, at a
    cell's shape: the compiled program holds the rotation's kernel pair
    for ``q`` and for ``k`` and, under the rotation's scope, NOTHING else:
    no half of a head is an array, no float32 copy of the operand, and the
    transposition to heads first is the kernels' own (what they write
    heads first ``Attention`` reads where it lies)."""
    from mxnet_tpu.ops import transformer

    t, heads, kv_heads = ROPE_LAYERS[cell]
    d, hidden = 128, 2048

    def loss(h, wq, wk, wv):
        q, k, v = h @ wq, h @ wk, h @ wv
        with jax.named_scope("ROTATION"):
            q = transformer.rope(q, heads, 1e4)
            k = transformer.rope(k, kv_heads, 1e4)
        out = pk.attention(q.reshape(1, t, heads, d),
                           k.reshape(1, t, kv_heads, d),
                           v.reshape(1, t, kv_heads, d), causal=True)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec(1, t, hidden), spec(hidden, heads * d),
        spec(hidden, kv_heads * d), spec(hidden, kv_heads * d)
    ).compile().as_text()
    entry = text[text.index("ENTRY"):]
    for n in (heads, kv_heads):
        rows = pk.rope_rows(n, d, t, jnp.bfloat16)
        for way in ("fwd", "bwd"):
            assert "rope_%s_bf16_r%d_h%d_d%d" % (way, rows, n, d) in entry
    scoped = [line for line in entry.splitlines() if "ROTATION" in line]
    assert len(scoped) >= 4
    assert all("custom-call(" in line and "rope_" in line
               for line in scoped), scoped
    assert not re.search(r"\[1,%d,\d+,64\]" % t, entry)
    assert not re.search(r"f32\[1,(%d,\d+|\d+,%d),128\]" % (t, t), entry)


def test_the_scaled_latent_pair_compiles_at_the_xing4_cells_shape(one_chip):
    """``LatentAttention(rope_scaling=, score_scale=)`` as the Xing4.0
    cell calls it (T 4,096, 32 heads of 128 + 64 / 128 from a latent of
    512, bf16; YaRN factor 64 over 4,096 positions, the scores times
    ``192^-0.5 x 2.0048``), forward and backward: the scale and the
    blended table change no kernel, the op takes the latent pair and the
    query pass once each way, and no single-key flash kernel is left."""
    from mxnet_tpu.models.xing4 import score_scale
    from mxnet_tpu.ops.transformer import latent_attention

    t, h, nope, rope, dv, latent = 4096, 32, 128, 64, 128, 512
    assert pk.latent_flash_takes(t, nope, rope, dv, jnp.bfloat16)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(*ins):
        return jnp.sum(latent_attention(
            *ins, num_heads=h, rope_dim=rope, v_head_dim=dv, theta=1e4,
            eps=1e-6, interleave=True, query_latent=768,
            rope_scaling=(64.0, 32.0, 1.0, 4096.0),
            score_scale=score_scale(192, 64.0, 1.0)).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(1, t, h * (nope + rope)), shape(1, t, latent + rope),
        shape(latent), shape(h * (nope + dv), latent)).compile().as_text()
    for which in ("fwd", "bwd"):
        assert len([line for line in text.splitlines()
                    if "flash2_%s_bf16_" % which in line
                    and "custom-call(" in line]) == 1, which
        assert len(re.findall(r"(?m)^\s*%%latent_query_%s_bf16[.\d]* = "
                              % which, text)) == 1
    assert "flash_fwd_" not in text and "flash_bwd_" not in text


def test_the_streams_mixing_writes_no_float32_stream_on_v5e(one_chip):
    """One sub-layer's ``HyperCoeff``, read and write at the Xing4.0
    cell's shape (4,096 tokens, 4 streams of 3,584, bf16), forward and
    backward: float32 is inside the fusions only — no op of the compiled
    program writes a float32 array as large as the stream — and the
    iterations stay ONE loop (a ``while``), not 20 copies of their body."""
    from mxnet_tpu.ops.transformer import hyper_coeff, hyper_mix

    tokens, n, c = 4096, 4, 3584

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, phi, bias, alpha, y):
        pre, post, res, _ = hyper_coeff(x, phi, bias, alpha, n, 20, 1e-6,
                                        (-30.0, 30.0))
        out = hyper_mix(x, res, y + hyper_mix(x, pre), post)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((tokens, n * c), jnp.bfloat16),
        spec((n * (n + 2), n * c), jnp.bfloat16),
        spec((n * (n + 2),), jnp.float32), spec((3,), jnp.float32),
        spec((tokens, c), jnp.bfloat16)).compile()
    text = compiled.as_text()
    entry = re.sub(r"\{[^{}]*\}", "", text[text.index("ENTRY"):])
    written = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (.*?) [\w\-]+\(", entry, re.M)]
    assert any("bf16[%d,%d]" % (tokens, n * c) in w for w in written)
    assert not [w for w in written if "f32[%d,%d]" % (tokens, n * c) in w]
    assert re.search(r" while\(", text)
    stream = tokens * n * c * 2
    # the cotangent of the stream and one more stream-sized temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * stream


def test_the_streams_passes_are_four_kernels_on_v5e(one_chip):
    """The same sub-layer through ``hyper_coeff_read`` and
    ``kernels.stream_write`` (PR 70): the coefficient pass with the read,
    and the write, are ONE kernel each each way at the block
    ``hyper_takes`` chose, each inside its own VMEM count, named for what
    they run and filed under the node's scope and ``hc_coeff`` /
    ``hc_mix``; the write reads the stream off the node, so the read's
    backward kernel's result is the stream's ONLY cotangent (no
    ``add_any`` of stream-sized arrays behind it), no float32 stream is
    written, and no fusion concatenates one."""
    from mxnet_tpu.ops.kernels import hyper
    from mxnet_tpu.ops.transformer import hyper_coeff_read

    tokens, n, c = 4096, 4, 3584
    block = pk.hyper_takes(tokens, n, c, jnp.bfloat16)
    assert block == 128
    for kernel in hyper.KERNELS:    # each is compiled under its own count
        assert hyper.hyper_vmem_bytes(
            block, n, c, 2, kernel) <= pk.common.VMEM_RAISED_LIMIT

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, phi, bias, alpha, y):
        with jax.named_scope("hc/layer0_attn_hc"):
            _, post, res, _, read, stream = hyper_coeff_read(
                x, phi, bias, alpha, n, 20, 1e-6, (-30.0, 30.0))
        with jax.named_scope("hc/layer0_attn_hc_write/hc_mix"):
            out = pk.stream_write(stream, res, y + read, post)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((tokens, n * c), jnp.bfloat16),
        spec((n * (n + 2), n * c), jnp.bfloat16),
        spec((n * (n + 2),), jnp.float32), spec((3,), jnp.float32),
        spec((tokens, c), jnp.bfloat16)).compile().as_text()
    for kernel, under in (
            ("read_fwd", "jvp(hc/layer0_attn_hc)/hc_coeff/"),
            ("read_bwd", "transpose(jvp(hc/layer0_attn_hc))/hc_coeff/"),
            ("write_fwd", "jvp(hc/layer0_attn_hc_write/hc_mix)/"),
            ("write_bwd", "transpose(jvp(hc/layer0_attn_hc_write/hc_mix))/")):
        calls = re.findall(
            r"(?m)^\s*(?:ROOT )?%%hc_%s_bf16_n4_c3584[.\d]* = .*" % kernel,
            text)
        assert len(calls) == 1, kernel
        assert under in calls[0], calls[0][-400:]
    entry = re.sub(r"\{[^{}]*\}", "", text[text.index("ENTRY"):])
    written = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (.*?) [\w\-]+\(", entry, re.M)]
    assert not [w for w in written if "f32[%d,%d]" % (tokens, n * c) in w]
    assert not re.search(r"(?m)^\s*%%\S*(pad|concatenate)\S* = bf16\[%d,%d\]"
                         % (tokens, n * c), entry)
    # the root's second result, the stream's cotangent, is the kernel's own
    root = re.search(r"(?m)^\s*ROOT %\S+ = .*? tuple\(%[\w.\-]+, (%[\w.\-]+)",
                     entry)
    assert re.search(
        r"(?m)^\s*%s = .*get-tuple-element\(%%hc_read_bwd_bf16_n4_c3584"
        % re.escape(root.group(1)), entry), root.group(0)


def test_the_streams_products_cotangents_go_at_the_default_precision():
    """What the read's backward kernel is held to (PR 70): autodiff of
    ``hyper_coeff``'s products ``phi x^T`` on a bf16 stream hands the
    products' float32 cotangent to BOTH backward products (``dphi`` and
    ``dx``) at the DEFAULT precision, which on the TPU is one bf16 pass:
    the float32 operand is rounded to bf16 in front of the MXU
    (``benchmarks/hyper_mix.py``'s row ``operand`` reads that on the
    chip). ``hc_read_bwd`` casts the same cotangents to the stream's type
    itself; a float32 stream multiplies at the highest, both ways."""
    tokens, n, c = 256, 4, 128
    rows = n * (n + 2)
    g = jax.ShapeDtypeStruct((rows, tokens), jnp.float32)
    for dtype, precision in ((jnp.bfloat16, "DEFAULT"),
                             (jnp.float32, "HIGHEST")):
        x = jax.ShapeDtypeStruct((tokens, n * c), dtype)
        phi = jax.ShapeDtypeStruct((rows, n * c), dtype)

        def pulled(x, phi, g):
            (_, pull) = jax.vjp(lambda x, phi: pk.stream_products(x, phi)[0],
                                x, phi)
            return pull(g)
        dots = re.findall(r"stablehlo\.dot_general.*", jax.jit(pulled).lower(
            x, phi, g).as_text())
        assert len(dots) == 2
        for dot in dots:
            assert "precision = [%s, %s]" % (precision, precision) in dot
            assert "tensor<%dx%dxf32>" % (rows, tokens) in dot
