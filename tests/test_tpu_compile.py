"""The Pallas kernels of the main path, compiled for a TPU v5e that is
described and not attached — what interpret mode cannot see: tiling,
alignment, fast-memory limits. Nothing runs, so nothing here says a result
is right or fast; `python chip_smoke.py` on the chip says that.

This is the ONLY file that describes a topology, and it does so inside a
fixture: the process that describes one loads the TPU library and keeps it
until it exits, so no import, `skipif`, `parametrize` argument or conftest
hook may do it (the other xdist workers would fail on the library's lock).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops.pallas import (conv_bn_relu, flash_attention,
                                            layer_norm, scale_shift_act)

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """The persistent cache is off around these compiles: an entry written
    for a described chip cannot be read back without one, and the next
    run would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_compile_cache):
    """compile_for_chip(fn, (shape, dtype), ...) -> optimized HLO text."""
    def compile_(fn, *shapes):
        specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in shapes]
        return jax.jit(fn).lower(*specs).compile().as_text()
    return compile_


def _with_grads(fn, n):
    """fn's output and its gradients wrt the first n arguments, as a
    training step uses them (a backward pass alone would let the
    compiler drop a forward kernel whose VJP is written in XLA)."""
    def fwd_bwd(*args):
        def scalar(*d):
            out = fn(*d, *args[n:])
            return jnp.sum(out.astype(jnp.float32)), out
        (_, out), grads = jax.value_and_grad(
            scalar, argnums=tuple(range(n)), has_aux=True)(*args[:n])
        return out, grads
    return fwd_bwd


# the causal LM of chip_smoke.py: GPT-2-base, batch 16, sequence 512
QKV = ((16, 12, 512, 64), BF16)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention(compile_for_chip, causal, direction):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)
    if direction == "bwd":
        fn = _with_grads(fn, 3)
    text = compile_for_chip(fn, QKV, QKV, QKV)
    # forward: one kernel; backward: forward again plus the one that
    # gives dq, dk and dv (a head's queries are resident at 512 tokens)
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)


def test_flash_attention_pads_a_ragged_sequence(compile_for_chip):
    """L=300, D=80: neither is a multiple of the 128-lane tile, so the
    wrapper pads both before the kernel sees them."""
    shape = ((2, 4, 300, 80), BF16)
    text = compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        shape, shape, shape)
    assert "tpu_custom_call" in text


def _custom_calls(text):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


# the benchmark's cell gpt2_train_b16_s1024, and BERT-base at 128 tokens
GPT2_CELL = ((16, 12, 1024, 64), BF16)
BERT_128 = ((32, 12, 128, 64), BF16)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_keeps_head_size_64(compile_for_chip, direction):
    """At the cell's own shape the kernels see the head size as it is: no
    operand or result of a custom-call ends in 128, and nothing in the
    program pads (the sequence is a multiple of the blocks too)."""
    import re

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)
    if direction == "bwd":
        fn = _with_grads(fn, 3)
    text = compile_for_chip(fn, GPT2_CELL, GPT2_CELL, GPT2_CELL)
    calls = _custom_calls(text)
    assert len(calls) == (1 if direction == "fwd" else 2)
    for line in calls:
        # the shapes of the call itself, before its metadata
        shapes = re.findall(r"(?:bf16|f32)\[([\d,]+)\]",
                            line.split("custom_call_target")[0])
        assert shapes and not [s for s in shapes if s.endswith(",128")], line
        assert any(s == "192,1024,64" for s in shapes)
    assert " pad(" not in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_short_sequence(compile_for_chip, direction):
    """BERT's shape when no padding mask is given: not causal, one block a
    head, two kernels with gradients."""
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=False, interpret=False)
    if direction == "bwd":
        fn = _with_grads(fn, 3)
    text = compile_for_chip(fn, BERT_128, BERT_128, BERT_128)
    assert len(_custom_calls(text)) == (1 if direction == "fwd" else 2)
    assert " pad(" not in text


def test_flash_attention_decode_step(compile_for_chip):
    """TransformerLM's cached decode where it has no mask: one query
    against a longer cache, offset > 0."""
    text = compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        ((8, 12, 1, 64), BF16), ((8, 12, 1000, 64), BF16),
        ((8, 12, 1000, 64), BF16))
    assert len(_custom_calls(text)) == 1


def test_flash_attention_streams_a_long_sequence(compile_for_chip):
    """65536 keys at d = 128 are beyond the VMEM budget (32768 resident
    positions in bf16): the key axis stays a grid axis, with scratch
    between its steps, and backward is two kernels (dq; dk and dv), each
    streaming the other sequence in major blocks of 32768."""
    shape = ((1, 2, 65536, 128), BF16)
    text = compile_for_chip(
        _with_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), 3),
        shape, shape, shape)
    calls = _custom_calls(text)
    assert len(calls) == 3
    for name in ("flash_attention_fwd)", "flash_attention_dq)",
                 "flash_attention_dkv)"):
        assert sum(name in line for line in calls) == 1, name


# the Mellum2 cell's attention: 32 query heads over 4 key/value heads of
# 128, 8192 tokens, a window of 1024 on three layers in four
MELLUM_Q = ((1, 32, 8192, 128), BF16)
MELLUM_KV = ((1, 4, 8192, 128), BF16)


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_flash_attention_window_and_grouped_heads_resident(compile_for_chip,
                                                           window):
    """At 8192 positions of head size 128 a head's K and V (Q and dO) are
    resident under the grant the plan asks Mosaic for, so backward is the
    ONE kernel `_bwd` and `_dq` does not run; the window and the grouped
    heads change index maps and loop bounds, not the kernels' number. K and
    V enter the kernels with their 4 heads: nothing repeats them to 32
    first."""
    import re

    text = compile_for_chip(
        _with_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=False), 3),
        MELLUM_Q, MELLUM_KV, MELLUM_KV)
    calls = _custom_calls(text)
    assert len(calls) == 2
    for name in ("flash_attention_fwd)", "flash_attention_bwd)"):
        line, = [c for c in calls if name in c]
        # results before the call, operands in its layout constraints
        shapes = re.findall(r"(?:bf16|f32)\[([\d,]+)\]",
                            line.split("metadata=")[0])
        assert "4,8192,128" in shapes and "32,8192,128" in shapes, line


# (query shape, key/value shape, dtype, window, kernels): the cell's two
# layer kinds, the longest resident sequences and the first streamed one
NEEDS = {
    "mellum2-window": ((1, 32, 8192, 128), (1, 4, 8192, 128), BF16, 1024, 2),
    "mellum2-full": ((1, 32, 8192, 128), (1, 4, 8192, 128), BF16, None, 2),
    "longest-resident": ((1, 8, 32768, 128), (1, 2, 32768, 128), BF16, None,
                         2),
    "longest-resident-window": ((1, 2, 32768, 128), (1, 2, 32768, 128), BF16,
                                1024, 2),
    "longest-resident-float32": ((1, 2, 16384, 128), (1, 2, 16384, 128),
                                 jnp.float32, None, 2),
    "first-streamed": ((1, 2, 33280, 128), (1, 2, 33280, 128), BF16, None,
                       3),
}


@pytest.mark.parametrize("case", NEEDS)
def test_flash_attention_compiles_inside_its_reckoned_need(
        compile_for_chip, monkeypatch, case):
    """Each kernel compiles with `vmem_limit_bytes` set to what `_plan`
    reckons it holds, WITHOUT the quarter `_grant` adds: the reckoning is
    an upper bound of what Mosaic lays out, so the grant is one too."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.ops.pallas.flash_attention")
    q_shape, kv_shape, dtype, window, kernels = NEEDS[case]
    asked = []

    def bare(vmem):
        asked.append(vmem)
        return {"vmem_limit_bytes": vmem}
    monkeypatch.setattr(fa, "_grant", bare)
    text = compile_for_chip(
        _with_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=False), 3),
        (q_shape, dtype), (kv_shape, dtype), (kv_shape, dtype))
    assert len(_custom_calls(text)) == kernels
    plan = fa._plan(q_shape[2], kv_shape[2], q_shape[3],
                    jnp.dtype(dtype).itemsize, False)
    needs = [plan.fwd_vmem, plan.dkv_vmem] + [plan.dq_vmem] * (kernels == 3)
    assert sorted(set(asked)) == sorted(set(needs))
    assert fa._merged(plan) == (kernels == 2)


# the JoyAI cell's latent layer: 1 x 8192 tokens, 32 heads, queries and
# keys of 128 + 64 (the 64 one part every head shares), values of 128
LATENT = {"q_n": ((1, 8192, 32 * 128), BF16), "q_r": ((1, 8192, 32 * 64), BF16),
          "kv": ((1, 8192, 32 * 256), BF16), "k_r": ((1, 8192, 64), BF16)}


def test_latent_attention_reads_its_parts_at_the_cells_shape(
        compile_for_chip, monkeypatch):
    """`ops.latent_attention` forward and backward at the JoyAI cell's
    shape, selected as on the chip: the two kernels compile with
    `vmem_limit_bytes` set to what `_plan` reckons (without `_grant`'s
    quarter), read q_n, kv and k_r as the products wrote them and q_r a
    head a row under the op scope `attention`, nothing in the program pads,
    and no buffer of a head of 192 keys, or of one padded to 256 lanes, is
    left in it."""
    import importlib
    import re

    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.ops import _raw
    fa = importlib.import_module(
        "incubator_mxnet_tpu.ops.pallas.flash_attention")
    asked = []

    def bare(vmem):
        asked.append(vmem)
        return {"vmem_limit_bytes": vmem}
    monkeypatch.setattr(fa, "_grant", bare)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = profiler.counters().get("ops/pallas.selected.latent_attention",
                                     0)
    text = compile_for_chip(
        _with_grads(lambda *a: _raw.latent_attention(*a, 32), 4),
        *LATENT.values())
    assert profiler.counters()[
        "ops/pallas.selected.latent_attention"] == before + 1
    calls = _custom_calls(text)
    assert [c.split(" = ")[0].strip().lstrip("%").split(".")[0]
            for c in calls] == ["flash_attention_fwd", "flash_attention_bwd"]
    plan = fa._plan(8192, 8192, 192, 2, False)
    assert fa._merged(plan) and sorted(set(asked)) == sorted(
        {plan.fwd_vmem, plan.dkv_vmem})
    for line in calls:
        head = line.split("metadata=")[0]
        # kv as kv_up wrote it, k_r shared, q_r a head a row
        assert "bf16[1,8192,8192]" in head and "bf16[1,8192,64]" in head
        assert "bf16[32,8192,64]" in head
        assert re.search(r'op_name="[^"]*\(attention\)+/flash_attention_'
                         r'(fwd|bwd)/pallas_call"', line), line[-300:]
    assert " pad(" not in text
    for shape in ("bf16[32,8192,256]", "bf16[32,8192,192]",
                  "bf16[1,8192,32,192]"):
        assert shape not in text, shape


# the Kimi-Linear cell's mixer: 1 x 8192 tokens, 32 heads of 128
KDA = ((1, 8192, 32, 128), BF16)
KDA_BETA = ((1, 8192, 32), jnp.float32)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_gated_delta_rule_at_the_cells_sizes(compile_for_chip, monkeypatch,
                                             direction):
    """The scan's two kernels at the Kimi-Linear cell's sizes, forward alone
    and with all five gradients, through `ops/_raw.py`'s rule: they read
    the (1, 8192, 32, 128) arrays as the mixer's stages hand them over (no
    heads-first copy, no transpose of an operand in the program), and each
    compiles with `vmem_limit_bytes` set to what `_plan` reckons it holds,
    WITHOUT the quarter `_grant` adds."""
    import importlib
    import re
    from incubator_mxnet_tpu.ops import _raw
    gdr = importlib.import_module(
        "incubator_mxnet_tpu.ops.pallas.gated_delta_rule")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    asked = []

    def bare(vmem):
        asked.append(vmem)
        return {"vmem_limit_bytes": vmem}
    monkeypatch.setattr(gdr, "_grant", bare)
    jax.clear_caches()      # the kernels' jits key on shapes, not on `_grant`
    fn = _raw._delta_rule_kernel
    if direction == "bwd":
        fn = _with_grads(fn, 5)
    text = compile_for_chip(fn, KDA, KDA, KDA, (KDA[0], jnp.float32),
                            KDA_BETA)
    jax.clear_caches()
    names = [re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line).group(1)
             for line in _custom_calls(text)]
    plan = gdr._plan(8192, 32, 128, 128, 2, False)
    if direction == "fwd":
        assert names == ["gated_delta_rule_fwd"] and asked == [plan.fwd_vmem]
    else:
        assert sorted(names) == ["gated_delta_rule_bwd",
                                 "gated_delta_rule_fwd"]
        assert sorted(asked) == sorted([plan.fwd_vmem, plan.bwd_vmem])
    assert (plan.block, plan.heads) == (512, 8)
    for line in _custom_calls(text):
        assert "[1,8192,32,128]" in line and "[1,32,8192,128]" not in line
    assert " transpose(" not in text.replace("transpose(jvp", "")


def test_grouped_matmul_at_the_cells_sizes(compile_for_chip):
    """The experts' three products forward and backward, 65536 rows held of
    which the group sizes say how many are live, 8 experts of 2304 x 896:
    six `gmm` and three `tgmm` kernels, no ragged-dot."""
    from incubator_mxnet_tpu.ops.pallas import grouped_matmul

    def experts(rows, gate, up, down, sizes):
        inner = (jax.nn.silu(grouped_matmul(rows, gate, sizes,
                                            interpret=False))
                 * grouped_matmul(rows, up, sizes, interpret=False))
        return grouped_matmul(inner, down, sizes, interpret=False)
    text = compile_for_chip(
        _with_grads(experts, 4), ((65536, 2304), BF16),
        ((8, 2304, 896), BF16), ((8, 2304, 896), BF16),
        ((8, 896, 2304), BF16), ((8,), jnp.int32))
    calls = _custom_calls(text)
    assert sum("tgmm" in c for c in calls) == 3
    assert len(calls) == 9 and "ragged-dot" not in text


def test_sparse_experts_keeps_no_residual_a_rung(one_chip, no_compile_cache,
                                                 monkeypatch,
                                                 record_property):
    """One expert layer of the Mellum2 cell, forward and backward (8192
    tokens of 2304, top-8 of 64 experts, 8 held): a `conditional` each way
    with a branch a rung, eleven grouped products a rung and two sums by
    token (`sum_by_token` under `moe/combine` forward, `moe/dispatch`
    backward, both owned by `moe`, where `moe_ms.train` counts them), and
    temporaries that do not grow with the ladder. Autodiff of the `switch`
    would make every branch return every branch's residuals, zero-filled
    (3,856,771,072 for this layer); what passes from the forward to the
    backward instead is its operands. The top rung's own buffers set the
    size."""
    import re

    from incubator_mxnet_tpu.ops import _raw

    # the selection asks the platform, and the platform here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()      # the rungs' traces of an earlier test

    def layer(x, router, gate, up, down):
        def scalar(*operands):
            y, load = _raw.sparse_experts(*operands, 8, 8)
            return jnp.sum(y.astype(jnp.float32)), load
        return jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(x, router, gate, up, down)
    specs = [jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
             for shape in ((8192, 2304), (64, 2304), (8, 2304, 896),
                           (8, 2304, 896), (8, 896, 2304))]
    ladder = _raw.row_capacities(8192 * 8, 8, 64)
    assert ladder == (10240, 65536)
    compiled = jax.jit(layer).lower(*specs).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    calls = _custom_calls(text)
    assert sum("tgmm" in c for c in calls) == 3 * len(ladder)
    assert len(calls) == 13 * len(ladder) and "ragged-dot" not in text
    # no float32 (tokens, D) sum is a scatter-add any more
    assert not re.search(r"= f32\[8192,2304\]\S* scatter\(", text)
    assert _owned_sums(text) == {("forward", "combine"): len(ladder),
                                 ("backward", "dispatch"): len(ladder)}
    temp = compiled.memory_analysis().temp_size_in_bytes
    record_property("temp_size_in_bytes", temp)
    assert temp < TEMP_OF_ONE_EXPERT_LAYER * 1.15, temp


# as compiled when the ladder was written (AOT, PR 29)
TEMP_OF_ONE_EXPERT_LAYER = 1_219_587_584


def _compiled_and_reserved(lowered, dump):
    """(the program compiled for the described chip, the HBM its runtime
    reserves for the temporaries): the final buffer assignment's HBM
    `preallocated-temp`, which is what the chip reserves to 16 KB (PERF.md,
    section 7). The benchmark's memory check (benchmark/run.py
    `reserved_is_filled`) wants it within 5% of `temp_size_in_bytes`."""
    import glob
    import os
    import re
    compiled = lowered.compile(compiler_options={
        "xla_dump_to": str(dump), "xla_dump_hlo_as_text": True})
    path = max(glob.glob(os.path.join(dump, "*buffer-assignment.txt")),
               key=os.path.getsize)
    with open(path) as f:
        hbm = [int(size) for size, color in re.findall(
            r"allocation \d+: size (\d+)(, color \d+)?, preallocated-temp",
            f.read()) if not color]
    return compiled, hbm[0]


def _lowered_counting(step, tokens, monkeypatch):
    """(the step lowered, the `sum_by_token` and `latent_attention`
    decisions its trace counted): `FusedTrainStep.lower` keeps the
    selection quiet, the chip's first call does not."""
    import contextlib

    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.ops import select
    monkeypatch.setattr(select, "quiet", contextlib.nullcontext)
    before = dict(profiler.counters())
    lowered = step.lower(tokens, tokens)
    return lowered, {k.split("/")[-1]: v - before.get(k, 0)
                     for k, v in profiler.counters().items()
                     if k.endswith((".sum_by_token", ".latent_attention"))
                     and v != before.get(k, 0)}


def _reserved_gap(compiled, reserved):
    temp = compiled.memory_analysis().temp_size_in_bytes
    return abs(temp - reserved) / temp


def _owned_sums(text):
    """The sums by token of a compiled step: `sum_by_token` under
    `moe/combine` in the forward and `moe/dispatch` in the backward, a pair
    a rung of every layer, each owned by `moe` (so in `moe_ms.train`)."""
    import os
    import re
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from lib import scopes              # the benchmark's reader of owners
    found = {}
    for line in _custom_calls(text):
        if re.match(r"\s*(?:ROOT )?%sum_by_token[.\d]* = ", line):
            name = re.search(r'op_name="([^"]*)"', line).group(1)
            parts = scopes.owner(name).split("/")
            assert "moe" in parts, name
            key = (scopes.phase(name), parts[parts.index("moe") + 1])
            found[key] = found.get(key, 0) + 1
    return found


LN = (((8192, 768), BF16), ((768,), BF16), ((768,), BF16))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_layer_norm(compile_for_chip, direction):
    def fn(x, g, b):
        return layer_norm(x, g, b, interpret=False)
    if direction == "bwd":
        fn = _with_grads(fn, 3)
    assert "tpu_custom_call" in compile_for_chip(fn, *LN)


# ResNet-50 NHWC, batch 128: the first and the last bottleneck stage
@pytest.mark.parametrize("shape", [(128, 56, 56, 256), (128, 7, 7, 2048)],
                         ids=["56x56x256", "7x7x2048"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_scale_shift_act(compile_for_chip, shape, direction):
    def fn(x, s, b):
        return scale_shift_act(x, s, b, act="relu", interpret=False)
    if direction == "bwd":
        fn = _with_grads(fn, 3)
    c = shape[-1]
    text = compile_for_chip(fn, (shape, BF16), ((c,), jnp.float32),
                            ((c,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel,pad", [(1, 0), (3, 1)], ids=["1x1", "3x3"])
def test_conv_bn_relu(compile_for_chip, kernel, pad):
    """1x1 is the fused matmul+epilogue kernel; 3x3 is XLA's convolution
    with the Pallas epilogue."""
    cin, cout = 256, 128

    def fn(x, w, g, b, m, v):
        return conv_bn_relu(x, w, g, b, m, v, pad=(pad, pad),
                            interpret=False)
    stat = ((cout,), jnp.float32)
    text = compile_for_chip(fn, ((128, 56, 56, cin), BF16),
                            ((kernel, kernel, cin, cout), BF16),
                            stat, stat, stat, stat)
    assert "tpu_custom_call" in text
    assert (" convolution(" in text) == (kernel == 3)


def test_max_pool_is_a_reduce_window_not_a_convolution(compile_for_chip):
    """ResNet-50's stem pool in float32. Pooling by patch extraction is a
    convolution, and the TPU feeds a float32 convolution bfloat16 operands:
    the first chip run returned rounded maxima and, from the padding value
    times the patch kernel's zeros, NaN."""
    from incubator_mxnet_tpu.ops import _raw
    text = compile_for_chip(
        lambda x: _raw.pooling(x, "max", (3, 3), (2, 2), (1, 1),
                               layout="NHWC"),
        ((8, 112, 112, 64), jnp.float32))
    # opcodes, "name(": the text also quotes this test's own name
    assert " reduce-window(" in text and " convolution(" not in text


def _lm_step_text(topo, monkeypatch, axes, mode):
    """The optimized HLO text of the whole FusedTrainStep program of a
    two-layer causal LM, compiled for the described chips."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models import TransformerLM
    from incubator_mxnet_tpu.models.transformer_lm import lm_loss
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    # the trace asks the platform, and the platform here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = TransformerLM(512, num_layers=2, units=128, hidden_size=512,
                        num_heads=2, max_length=128, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.02))
    net.cast("bfloat16")
    n = int(np.prod(list(axes.values())))
    step = FusedTrainStep(net, lambda out, y: lm_loss(out, y).mean(),
                          mx.optimizer.create("adam", multi_precision=True),
                          mesh=make_mesh(axes, topo.devices[:n]),
                          sharding=mode)
    tokens = nd.array(np.zeros((8, 128), np.int32))
    return step.lower(tokens, tokens).compile().as_text()


@pytest.mark.parametrize("axes,mode", [({"dp": 1}, "dp"), ({"dp": 4}, "dp"),
                                       ({"dp": 2, "mp": 2}, "auto")],
                         ids=["one-chip", "dp4", "dp2xmp2"])
def test_lm_train_step(topo, no_compile_cache, monkeypatch, axes, mode):
    """The whole FusedTrainStep program of a two-layer causal LM. On one
    chip it holds the kernels; over four, where jax refuses to partition a
    Mosaic kernel ("wrap the call in a shard_map"), the selection layer
    keeps them out and the compiler's all-reduce is there instead."""
    text = _lm_step_text(topo, monkeypatch, axes, mode)
    one_chip = all(size == 1 for size in axes.values())
    assert ("tpu_custom_call" in text) == one_chip
    assert ("all-reduce(" in text) == (not one_chip)


def test_dense_steps_never_ask_for_the_sum_by_token(topo, no_compile_cache,
                                                    monkeypatch):
    """The GPT-2 and ResNet-50 cells' steps run no expert layer: the LM's
    step compiled for the chip and ResNet-50's lowered for it ask ops/
    select.py's row `sum_by_token` nothing and hold no such kernel, so the
    row leaves their programs as they were (their lowered steps at full
    width equal the parent's string for string: PERF.md, PR 35)."""
    import importlib.util
    import json
    import os

    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.ops import select
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    asked = []
    monkeypatch.setattr(select, "sum_by_token",
                        lambda *a: asked.append(a) or False)
    texts = [_lm_step_text(topo, monkeypatch, {"dp": 1}, "dp")]
    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    with open(os.path.join(configs, "resnet50_v1.json")) as f:
        doc = json.load(f)
    doc.update(doc.pop("rehearse"))
    spec = importlib.util.spec_from_file_location(
        "resnet50_config", os.path.join(configs, "resnet50_v1.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    step = FusedTrainStep(model.net(doc, 1), model.loss(doc),
                          model.optimizer(doc),
                          mesh=make_mesh({"dp": 1}, topo.devices[:1]),
                          sharding="dp")
    x = nd.array(np.zeros((2, 64, 64, 3), np.float32)).astype("bfloat16")
    y = nd.array(np.zeros((2,), np.float32))
    texts.append(step.lower(x, y).as_text())
    assert not asked
    # (the compiled text names this test among its stack frames)
    assert not [line for text in texts for line in text.splitlines()
                if "custom_call" in line and "sum_by_token" in line]


def _entry_instructions(text):
    """(result shapes, op_name) of each instruction of the ENTRY
    computation that writes memory (fusions whole; no tuple element,
    bitcast or parameter, which only name a buffer)."""
    import re
    body = text[text.index("\nENTRY "):]
    body = body[:body.index("\n}")]
    found = []
    for line in body.splitlines()[1:]:
        head = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(",
                        line)
        if head and head.group(2) not in ("get-tuple-element", "bitcast",
                                          "tuple", "parameter"):
            shapes = [tuple(int(n) for n in dims.split(",") if n)
                      for dims in re.findall(r"\[([\d,]*)\]", head.group(1))]
            op_name = re.search(r'op_name="([^"]*)"', line)
            found.append((shapes, op_name.group(1) if op_name else ""))
    return found


def test_lm_train_step_takes_the_loss_over_the_logits_as_written(
        topo, no_compile_cache, monkeypatch):
    """The shifted loss of the one-chip LM step (`lm_loss`) reads the
    head's (B, L, V) logits where the head wrote them: no instruction
    yields the (B (L-1), V) slice of them, and in the forward the loss
    writes no array of the logits' size, so it keeps none for the backward
    (its rule keeps the logits and a float32 log-sum-exp a row); the one
    the head writes is its output. (The blocks' feed-forward is as wide as
    this vocabulary: their arrays are theirs.)"""
    import math
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from lib import scopes              # the benchmark's reader of owners
    b, length, vocab = 8, 128, 512      # _lm_step_text's batch and net
    text = _lm_step_text(topo, monkeypatch, {"dp": 1}, "dp")
    assert f"[{b * (length - 1)},{vocab}]" not in text
    size = {b * length * vocab, b * (length - 1) * vocab}
    written = [(scopes.owner(op_name).split("/"), shape)
               for shapes, op_name in _entry_instructions(text)
               for shape in shapes
               if math.prod(shape) in size
               and scopes.phase(op_name) == "forward"]
    assert not [shape for owner, shape in written if "loss" in owner]
    head = [shape for owner, shape in written
            if len(owner) == 1 and owner[0].startswith("transformer_lm")]
    assert head == [(b, length, vocab)], written
    # the loss's forward is there, under its op scope
    assert any("/cross_entropy/" in op_name
               and scopes.phase(op_name) == "forward"
               for _, op_name in _entry_instructions(text))


KERNEL_NAMES = {"flash_attention_fwd": "attention",
                "flash_attention_bwd": "attention",
                "layer_norm_fwd": "layer_norm"}


def test_lm_train_step_kernels_are_named_and_owned(topo, no_compile_cache,
                                                   monkeypatch):
    """Every Mosaic kernel of the one-chip LM step carries its
    `pl.pallas_call(name=)` as the instruction's name and in its
    `op_name`, under the op scope of ops/_raw.py (`attention`,
    `layer_norm`) and the blocks that called it: a device trace names the
    kernel and its owner, no shape needed (docs/profiler.md)."""
    import re
    text = _lm_step_text(topo, monkeypatch, {"dp": 1}, "dp")
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # 2 layers: attention forward and backward; 2 x 2 + 1 layer norms
    assert len(calls) == 2 * 2 + 5
    seen = set()
    for line in calls:
        instruction = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ",
                               line).group(1)
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert instruction in KERNEL_NAMES, line[:120]
        path = op_name.split("/")
        assert path[-2:] == [instruction, "pallas_call"]
        assert path[-3] == KERNEL_NAMES[instruction]
        assert path[0] == "jit(train_step)"
        assert re.fullmatch(r"(transpose\()?jvp\(transformer_lm_\d+\)\)?",
                            path[1])
        assert ("transpose(" in op_name) == instruction.endswith("_bwd")
        seen.add(instruction)
    assert seen == set(KERNEL_NAMES)
    # head size 64: the attention scope pads nothing and its kernels see 64
    assert not [line for line in text.splitlines()
                if " pad(" in line and "/attention" in line]
    assert all("[16,128,64]" in line for line in calls
               if "flash_attention" in line)


def test_mellum2_cell_train_step(topo, no_compile_cache, monkeypatch,
                                 tmp_path):
    """The FusedTrainStep program of the benchmark's Mellum2 cell at its
    published widths and its 1 x 8192 tokens, one sliding and one full layer
    of the period of four (half the cell's depth: the other two repeat the
    sliding one): it compiles for the described v5e inside a chip's memory,
    with the attention kernels, the grouped products (a set for each
    capacity the expert layer's row buffers may take) and every new op
    scope as the owners of their operations (docs/profiler.md)."""
    import importlib.util
    import json
    import os
    import re

    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    with open(os.path.join(configs, "mellum2_12b_a2.5b_ep8.json")) as f:
        doc = json.load(f)
    del doc["rehearse"]
    doc.update(num_hidden_layers=2, layer_types=doc["layer_types"][2:4])
    spec = importlib.util.spec_from_file_location(
        "mellum2_config", os.path.join(configs, "mellum2_12b_a2.5b_ep8.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = FusedTrainStep(model.net(doc, 1), model.loss(doc),
                          model.optimizer(doc),
                          mesh=make_mesh({"dp": 1}, topo.devices[:1]),
                          sharding="dp")
    tokens = nd.array(np.zeros((1, 8192), np.int32))
    lowered, asked = _lowered_counting(step, tokens, monkeypatch)
    # the row is asked once a sparse layer, as on the chip
    assert asked == {"pallas.selected.sum_by_token": 2}, asked
    compiled, reserved = _compiled_and_reserved(lowered, tmp_path)
    held = compiled.memory_analysis()
    assert (held.argument_size_in_bytes + held.temp_size_in_bytes
            < 15 * 2 ** 30)
    # AOT, PR 35: 4.16% (the parent 4.18%)
    assert _reserved_gap(compiled, reserved) < 0.05
    text = compiled.as_text()
    kernels = {}
    for line in _custom_calls(text):
        name = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ",
                        line).group(1)
        kernels[name] = kernels.get(name, 0) + 1
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        scope = ("attention" if name.startswith("flash") else "moe"
                 if name == "sum_by_token" else "moe/experts")
        assert f"/{scope}/" in op_name, line[:160]
    # a rung of a layer: three products forward; two made again, three for
    # the rows' gradient and three `tgmm` for the weights' in the backward
    from incubator_mxnet_tpu.ops import _raw
    rungs = len(_raw.row_capacities(8192 * 8, 8, 64))
    assert kernels == {"flash_attention_fwd": 2, "flash_attention_bwd": 2,
                       "gmm": 2 * 8 * rungs,
                       "tgmm": 2 * 3 * rungs,
                       "sum_by_token": 2 * 2 * rungs}
    assert _owned_sums(text) == {("forward", "combine"): 2 * rungs,
                                        ("backward", "dispatch"): 2 * rungs}
    assert text.count(" conditional(") == 2 * 2
    for scope in ("rms_norm", "rope", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine"):
        assert f"/{scope}/" in text, scope
    assert "ragged-dot" not in text


def test_kimi_linear_cell_train_step(topo, no_compile_cache, monkeypatch,
                                     record_property, tmp_path):
    """The FusedTrainStep program of the benchmark's Kimi-Linear cell at its
    published widths and its 1 x 8192 tokens, the leading KDA + dense layer
    and the MLA + experts layer (two of the cell's five: the other three
    repeat the KDA mixer and the expert layer): it compiles for the
    described v5e inside a chip's memory, with the flash kernels on the
    latent layer's parts (keys of 128 + a shared 64, read where the products
    wrote them) beside values of 128, the chunked delta rule
    as two Pallas kernels (forward, and its own backward) and no `while`,
    the grouped products and every new op scope as the owners of their
    operations (docs/profiler.md)."""
    import importlib.util
    import json
    import os
    import re

    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    name = "kimi_linear_48b_a3b_ep32"
    with open(os.path.join(configs, name + ".json")) as f:
        doc = json.load(f)
    del doc["rehearse"]
    doc.update(num_hidden_layers=2,
               layer_types=["linear_attention", "latent_attention"],
               mlp_layer_types=["dense", "sparse"])
    spec = importlib.util.spec_from_file_location(
        "kimi_config", os.path.join(configs, name + ".py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = FusedTrainStep(model.net(doc, 1), model.loss(doc),
                          model.optimizer(doc),
                          mesh=make_mesh({"dp": 1}, topo.devices[:1]),
                          sharding="dp")
    tokens = nd.array(np.zeros((1, 8192), np.int32))
    lowered, asked = _lowered_counting(step, tokens, monkeypatch)
    # each row is asked once a layer it serves, as on the chip
    assert asked == {"pallas.selected.sum_by_token": 1,
                     "pallas.selected.latent_attention": 1}, asked
    compiled, reserved = _compiled_and_reserved(lowered, tmp_path)
    held = compiled.memory_analysis()
    record_property("temp_size_in_bytes", held.temp_size_in_bytes)
    assert (held.argument_size_in_bytes + held.temp_size_in_bytes
            < 15 * 2 ** 30)
    # AOT, PR 35: 0.35% (the parent 1.05%)
    assert _reserved_gap(compiled, reserved) < 0.05
    text = compiled.as_text()
    kernels = {}
    for line in _custom_calls(text):
        kernel = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ",
                          line).group(1)
        kernels[kernel] = kernels.get(kernel, 0) + 1
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        scope = ("latent_attention/attention" if kernel.startswith("flash")
                 else "linear_attention/scan" if kernel.startswith("gated")
                 else "moe" if kernel == "sum_by_token" else "moe/experts")
        assert f"/{scope}/" in op_name, line[:160]
        if kernel.startswith("gated"):
            assert ("transpose(" in op_name) == kernel.endswith("_bwd")
            assert op_name.endswith(f"/{kernel}/pallas_call")
        if kernel.startswith("flash"):
            # 32 heads of 8192: q_r a head a row of 64; kv as the product
            # wrote it, (1, 8192, 32 x 256); k_r shared; nothing of 256 lanes
            assert "[32,8192,64]" in line and "[1,8192,8192]" in line
            assert "[1,8192,64]" in line and "[32,8192,256]" not in line
    from incubator_mxnet_tpu.ops import _raw
    rungs = len(_raw.row_capacities(8192 * 8, 8, 256))
    assert rungs == 2 and _raw.row_capacities(8192 * 8, 8, 256)[0] == 2560
    assert kernels == {"flash_attention_fwd": 1, "flash_attention_bwd": 1,
                       "gated_delta_rule_fwd": 1, "gated_delta_rule_bwd": 1,
                       "gmm": 8 * rungs, "tgmm": 3 * rungs,
                       "sum_by_token": 2 * rungs}
    assert _owned_sums(text) == {("forward", "combine"): rungs,
                                        ("backward", "dispatch"): rungs}
    import sys
    sys.path.insert(0, os.path.dirname(configs))
    from lib import scopes              # the benchmark's reader of owners
    owners = {scopes.owner(name)
              for name in re.findall(r'op_name="([^"]*)"', text)}
    for scope in ("linear_attention/conv", "linear_attention/gate",
                  "linear_attention/scan", "linear_attention/out_norm",
                  "linear_attention_cell_0/projections",
                  "latent_attention/kv_down", "latent_attention/kv_up",
                  "latent_attention/attention", "moe/router", "moe/shared",
                  "moe/experts"):
        assert any(f"/{scope}" in owner + "/" for owner in owners), scope
    assert not any("(" in owner or ")" in owner for owner in owners)
    # the mixer's backward takes its stages back one by one, so a wrapped
    # name never holds a `/` (lib/scopes.py splits owners there)
    assert "/linear_attention/transpose(jvp(conv))/" in text
    # the scan is its two kernels: no loop over steps of 8 chunks is left
    assert "/linear_attention/scan/while" not in text


def test_zaya1_cell_train_step(topo, no_compile_cache, monkeypatch,
                               record_property, tmp_path):
    """The FusedTrainStep program of the benchmark's ZAYA1 cell at its
    published widths and its 1 x 8192 tokens, layers 0 and 1 (of the cell's
    six alike: the first has no router state to average, the second has):
    it compiles for the described v5e inside a chip's memory, with the flash
    kernels at 8 query heads over 2 key/value heads of 128 inside the
    latent, the grouped products of one assignment a token on both rungs,
    and the mixer's and the router's op scopes as the owners of their
    operations, forward and backward (docs/profiler.md)."""
    import importlib.util
    import json
    import os
    import re

    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    name = "zaya1_8b_ep2"
    with open(os.path.join(configs, name + ".json")) as f:
        doc = json.load(f)
    del doc["rehearse"]
    doc.update(num_hidden_layers=2)
    spec = importlib.util.spec_from_file_location(
        "zaya1_config", os.path.join(configs, name + ".py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = FusedTrainStep(model.net(doc, 1), model.loss(doc),
                          model.optimizer(doc),
                          mesh=make_mesh({"dp": 1}, topo.devices[:1]),
                          sharding="dp")
    tokens = nd.array(np.zeros((1, 8192), np.int32))
    lowered, asked = _lowered_counting(step, tokens, monkeypatch)
    # the row is asked once a sparse layer, as on the chip
    assert asked == {"pallas.selected.sum_by_token": 2}, asked
    compiled, reserved = _compiled_and_reserved(lowered, tmp_path)
    held = compiled.memory_analysis()
    record_property("argument_size_in_bytes", held.argument_size_in_bytes)
    record_property("temp_size_in_bytes", held.temp_size_in_bytes)
    assert (held.argument_size_in_bytes + held.temp_size_in_bytes
            < 15 * 2 ** 30)
    # AOT, PR 35: 4.25% (the parent 5.76% at these two layers; the cell's
    # five read 1.68%)
    assert _reserved_gap(compiled, reserved) < 0.05
    text = compiled.as_text()
    kernels = {}
    for line in _custom_calls(text):
        kernel = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ",
                          line).group(1)
        kernels[kernel] = kernels.get(kernel, 0) + 1
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        scope = ("compressed_attention/attention"
                 if kernel.startswith("flash") else "moe"
                 if kernel == "sum_by_token" else "moe/experts")
        assert f"/{scope}/" in op_name, line[:160]
        if kernel.startswith("flash"):
            # 8 query heads of 8192 x 128; keys and values of 2 heads
            assert "[8,8192,128]" in line and "[2,8192,128]" in line
    from incubator_mxnet_tpu.ops import _raw
    ladder = _raw.row_capacities(8192, 8, 16)
    assert ladder == (5120, 8192)
    assert kernels == {"flash_attention_fwd": 2, "flash_attention_bwd": 2,
                       "gmm": 2 * 8 * len(ladder),
                       "tgmm": 2 * 3 * len(ladder),
                       "sum_by_token": 2 * 2 * len(ladder)}
    assert _owned_sums(text) == {
        ("forward", "combine"): 2 * len(ladder),
        ("backward", "dispatch"): 2 * len(ladder)}
    import sys
    sys.path.insert(0, os.path.dirname(configs))
    from lib import scopes              # the benchmark's reader of owners
    op_names = re.findall(r'op_name="([^"]*)"', text)
    owners = {scopes.owner(op_name) for op_name in op_names}
    assert not any("(" in owner or ")" in owner for owner in owners)
    for scope in ("compressed_attention/shift", "compressed_attention/conv",
                  "compressed_attention/mean", "compressed_attention/norm",
                  "compressed_attention/rope",
                  "compressed_attention/attention", "moe/router/down",
                  "moe/router/average", "moe/router/mlp", "moe/dispatch",
                  "moe/experts", "moe/combine", "rms_norm"):
        for phase in ("forward", "backward"):
            assert any(f"/{scope}/" in scopes.owner(op_name) + "/"
                       and scopes.phase(op_name) == phase
                       for op_name in op_names), (scope, phase)
    # the first layer's router has no state to average
    assert not any("sparse_experts_0/moe/router/average" in owner
                   for owner in owners)
    assert "ragged-dot" not in text


def test_joyai_flash_cell_train_step(topo, no_compile_cache, monkeypatch,
                                     record_property, tmp_path):
    """The FusedTrainStep program of the benchmark's JoyAI-LLM-Flash cell at
    its published widths and its 1 x 8192 tokens, the leading dense layer,
    one expert layer and the MTP module (three of the cell's six latent
    layers: the other three repeat the expert layer): it compiles for the
    described v5e inside a chip's memory, with the flash kernels on the
    latent parts (keys of 128 + a shared 64, read where the products wrote
    them) beside values of 128 in every latent layer,
    the MTP block's among them, and the query's rank, the rotation and the
    MTP module as the owners of their operations, forward and backward
    (docs/profiler.md)."""
    import importlib.util
    import json
    import os
    import re
    import sys

    import numpy as np

    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh

    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    name = "joyai_llm_flash_ep32"
    with open(os.path.join(configs, name + ".json")) as f:
        doc = json.load(f)
    del doc["rehearse"]
    doc.update(num_hidden_layers=2)
    spec = importlib.util.spec_from_file_location(
        "joyai_config", os.path.join(configs, name + ".py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = FusedTrainStep(model.net(doc, 1), model.loss(doc),
                          model.optimizer(doc),
                          mesh=make_mesh({"dp": 1}, topo.devices[:1]),
                          sharding="dp")
    tokens = nd.array(np.zeros((1, 8192), np.int32))
    lowered, asked = _lowered_counting(step, tokens, monkeypatch)
    # the sum by token once an expert layer, layer 1's and the MTP block's;
    # the kernels on the latent parts once a latent layer, the MTP block's too
    assert asked == {"pallas.selected.sum_by_token": 2,
                     "pallas.selected.latent_attention": 3}, asked
    compiled, reserved = _compiled_and_reserved(lowered, tmp_path)
    held = compiled.memory_analysis()
    record_property("temp_size_in_bytes", held.temp_size_in_bytes)
    assert (held.argument_size_in_bytes + held.temp_size_in_bytes
            < 15 * 2 ** 30)
    # compiled for a described v5e, the cell's five layers and the MTP
    # module read 1.0%
    assert _reserved_gap(compiled, reserved) < 0.05
    text = compiled.as_text()
    kernels = {}
    for line in _custom_calls(text):
        kernel = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ",
                          line).group(1)
        kernels[kernel] = kernels.get(kernel, 0) + 1
        if kernel.startswith("flash"):
            assert "/latent_attention/attention/" in line, line[:160]
            assert "[32,8192,64]" in line and "[1,8192,8192]" in line
            assert "[1,8192,64]" in line and "[32,8192,256]" not in line
    from incubator_mxnet_tpu.ops import _raw
    rungs = len(_raw.row_capacities(8192 * 8, 8, 256))
    assert kernels == {"flash_attention_fwd": 3, "flash_attention_bwd": 3,
                       "gmm": 2 * 8 * rungs, "tgmm": 2 * 3 * rungs,
                       "sum_by_token": 2 * 2 * rungs}
    sys.path.insert(0, os.path.dirname(configs))
    from lib import scopes              # the benchmark's reader of owners
    op_names = re.findall(r'op_name="([^"]*)"', text)
    owners = {scopes.owner(op_name) for op_name in op_names}
    assert not any("(" in owner or ")" in owner for owner in owners)
    for scope in ("latent_attention/q_down", "latent_attention/q_up",
                  "latent_attention/kv_down", "latent_attention/rope",
                  "latent_attention/kv_up", "latent_attention/attention"):
        for phase in ("forward", "backward"):
            assert any(f"/{scope}/" in scopes.owner(op_name) + "/"
                       and scopes.phase(op_name) == phase
                       for op_name in op_names), (scope, phase)
    mtp = [op_name for op_name in op_names
           if "mtp" in scopes.owner_class(scopes.owner(op_name)).split("/")]
    for phase in ("forward", "backward"):
        assert any(scopes.phase(op_name) == phase for op_name in mtp), phase
    # the MTP block's own attention and experts, and the shared head's
    # second product, are the module's
    assert any("/latent_attention/attention/" in scopes.owner(op_name) + "/"
               for op_name in mtp)
    assert any("/moe/experts" in scopes.owner(op_name) for op_name in mtp)
    assert "ragged-dot" not in text
