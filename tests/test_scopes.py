"""The names the program compiles into a fused step (docs/profiler.md,
"Names in a device trace"), held on the compiled program's own text: every
operation has an owner (Gluon blocks, then the op scopes `attention`,
`layer_norm`, `batch_norm`, `cross_entropy`, then a Pallas kernel's name)
and a phase. The grammar is the benchmark reader's, benchmark/lib/
scopes.py: what the program says and what the reader understands are held
together here."""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import scopes  # noqa: E402


@pytest.mark.parametrize("op_name,owner,phase", [
    ("jit(train_step)/jvp(transformer_lm_0)/transformer_lm_cell_3/"
     "causal_self_attention_3/dense_14/dot_general",
     "transformer_lm_0/transformer_lm_cell_3/causal_self_attention_3/"
     "dense_14", "forward"),
    ("jit(train_step)/transpose(jvp(transformer_lm_0))/cell_1/attention/"
     "flash_attention_dq/pallas_call",
     "transformer_lm_0/cell_1/attention/flash_attention_dq", "backward"),
    ("jit(train_step)/jvp(loss)/softmax_cross_entropy_loss_0/"
     "jit(log_softmax)/reduce_max", "loss/softmax_cross_entropy_loss_0",
     "forward"),
    ("jit(train_step)/transpose(jvp(loss))/div", "loss", "backward"),
    ("jit(train_step)/optimizer/mul", "optimizer", "optimizer"),
    ("jit(train_step)/optimizer/jit(clip)/max", "optimizer", "optimizer"),
    # a nested jit inside a path names a function, not a block
    ("jit(train_step)/jvp(resnet_v1_0)/stage1/jit(relu)/max",
     "resnet_v1_0/stage1", "forward"),
    # remat: the recomputed forward runs in the backward pass
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "transformer_lm_0/layer_norm_4/layer_norm/rsqrt",
     "transformer_lm_0/layer_norm_4/layer_norm", "backward"),
    ("jit(train_step)/jvp(jvp())/checkpoint/transformer_lm_0/embedding_0/"
     "jit(_take)/gather", "transformer_lm_0/embedding_0", "forward"),
    # XLA joined two operations' names: the first decides both
    ("jit(train_step)/transpose(jvp(net_0))/attention/transpose;"
     "jit(train_step)/jvp(net_0)/dense_0/dot_general",
     "net_0/attention", "backward"),
    # the step's scan wrapper (run_k)
    ("jit(scan_fn)/while/body/jvp(net_0)/dense_0/dot_general",
     "while/body/net_0/dense_0", "forward"),
    # not traced under the step: parameters, what the compiler made
    ("train_raws[3]", "", "other"),
    ("reduce_sum", "", "other"),
    ("jit(train_step)/convert_element_type", "", "other"),
    ("", "", "other"),
])
def test_owner_and_phase_of_an_op_name(op_name, owner, phase):
    assert scopes.owner(op_name) == owner
    assert scopes.phase(op_name) == phase


def test_owner_class_joins_the_layers():
    assert scopes.owner_class(
        "transformer_lm_0/transformer_lm_cell_11/dense_47") == \
        "transformer_lm/transformer_lm_cell/dense"
    assert scopes.owner_class("resnet_v1_0/stage3/conv2d_31") == \
        "resnet_v1/stage/conv2d"
    assert scopes.owner_class("") == ""


# -- the compiled step ------------------------------------------------------

def _op_names(step, x, y):
    text = step.lower(x, y).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _toy_lm():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models import TransformerLM
    from incubator_mxnet_tpu.models.transformer_lm import lm_loss
    from incubator_mxnet_tpu.parallel import FusedTrainStep

    net = TransformerLM(64, num_layers=2, units=32, hidden_size=64,
                        num_heads=2, max_length=16, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.02))
    step = FusedTrainStep(net, lambda out, y: lm_loss(out, y).mean(),
                          mx.optimizer.create("adam"))
    tokens = nd.array(np.zeros((2, 16), np.int32))
    return net, step, tokens, tokens


def _toy_resnet():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.models import get_model
    from incubator_mxnet_tpu.parallel import FusedTrainStep

    net = get_model("resnet18_v1", classes=10, layout="NHWC")
    net.initialize(init=mx.init.Xavier())
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.create("sgd", momentum=0.9))
    x = nd.array(np.zeros((2, 32, 32, 3), np.float32))
    y = nd.array(np.zeros((2,), np.float32))
    return net, step, x, y


def _owners_by_phase(names):
    found = {phase: set() for phase in scopes.PHASES}
    for name in names:
        found[scopes.phase(name)].update(scopes.owner(name).split("/"))
    return found


@pytest.mark.parametrize("pallas", ["0", "force"])
@pytest.mark.parametrize("make,op_scopes", [
    (_toy_lm, {"attention", "layer_norm", "cross_entropy"}),
    (_toy_resnet, {"batch_norm", "cross_entropy"}),
], ids=["transformer_lm", "resnet18_v1"])
def test_every_operation_of_a_fused_step_has_an_owner(monkeypatch, make,
                                                      op_scopes, pallas):
    """Kernels forced off and forced on (interpreted here): the op scope
    covers both of ops/select.py's branches, so the owner is the same."""
    monkeypatch.setenv("MXTPU_PALLAS", pallas)
    net, step, x, y = make()
    names = _op_names(step, x, y)
    traced = [n for n in names if "jvp(" in n]
    assert len(traced) > 100
    # by class: the compile cache's key leaves names out, so the text may
    # be that of an equal program of an earlier net, `..._0` for `..._1`
    roots = (scopes.owner_class(net.name), "loss")
    owned = [n for n in traced
             if scopes.owner_class(scopes.owner(n).split("/")[0]) in roots]
    assert len(owned) >= 0.95 * len(traced)
    by_phase = _owners_by_phase(names)
    assert op_scopes <= by_phase["forward"]
    assert op_scopes <= by_phase["backward"]
    assert "loss" in by_phase["forward"] and "loss" in by_phase["backward"]
    # the optimizer's updates, and nothing of a block, under `optimizer`
    updates = [n for n in names if scopes.phase(n) == "optimizer"]
    assert len(updates) > 10
    assert all(scopes.owner(n) == "optimizer" for n in updates)
    assert not [n for n in names
                if scopes.phase(n) == "other" and scopes.owner(n)]


def test_blocks_are_named_in_a_traced_program_only():
    """Eager ops are compiled once, under whichever block ran them first:
    a block name there would be another block's, so none is written. A
    block names its operations while the package traces a program (a
    fused step, a hybridized or frozen forward)."""
    import jax

    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon.parameter import _ParamTraceScope

    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    x = np.zeros((2, 3), np.float32)

    def text():     # lowered, not compiled: a cache may serve old names
        return jax.jit(lambda raw: net(nd.NDArray(raw))._data).lower(
            x).as_text(debug_info=True)

    assert net.name + "/" not in text()
    with _ParamTraceScope({}):
        assert f"/{net.name}/dot_general" in text()
