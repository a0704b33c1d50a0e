"""The program's own names in a profiler trace: the device scopes of the
scanned step (``jax.named_scope``) in the compiled epoch program's
``op_name`` metadata, and the host spans (``jax.profiler.TraceAnnotation``)
of the epoch loop in a trace recorded on the CPU."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw
from repro.tig.batching import build_batch_program, make_tables
from repro.tig.data import synthetic_tig
from repro.tig.engine import make_eval_epoch, make_train_epoch
from repro.tig.models import TIGConfig, init_params, init_state
from repro.tig.protocol import device_batches, split_views
from repro.tig.sampler import ChronoNeighborIndex
from repro.tig.train import _stage_tcsr, train_single

CFG = TIGConfig(flavor="tgn", dim=16, dim_time=8, dim_edge=16, dim_node=16,
                num_neighbors=4, batch_size=32)

# stage -> whether its ops sit under value_and_grad (forward as jvp(...),
# gradient as transpose(jvp(...))) and whether the stage has a gradient
STAGES = {
    "tig.sample": (False, False),
    "tig.memory.flush": (True, True),
    "tig.embed": (True, True),
    "tig.decode": (True, True),
    "tig.memory.stash": (True, False),
    "tig.optimizer": (False, False),
}


def _epoch_program(cfg):
    """The compiled text of one raw-edge (``plan="device"``) epoch."""
    g = synthetic_tig("tiny", seed=0)
    tr = split_views(g).train
    index = ChronoNeighborIndex(tr.src, tr.dst, tr.t, tr.eidx, g.num_nodes,
                                cfg.num_neighbors, cfg.batch_size)
    batches, _ = build_batch_program(tr, cfg, np.random.default_rng(0),
                                     index=index, plan="device")
    tables = {k: jnp.asarray(v)
              for k, v in make_tables(g.edge_feat, g.node_feat).items()}
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(lr=1e-3)
    fn = make_train_epoch(cfg, opt)
    return fn.lower(params, opt.init(params), init_state(cfg, g.num_nodes),
                    device_batches(batches), tables,
                    tcsr=_stage_tcsr(index)).compile().as_text()


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_epoch_program_carries_every_stage_scope(kernels):
    cfg = dataclasses.replace(CFG, use_pallas=kernels == "interpret",
                              kernel_backend="interpret")
    text = _epoch_program(cfg)
    assert text.startswith("HloModule jit_scan_train_epoch,")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope, (under_grad, has_grad) in STAGES.items():
        fwd = f"/jvp({scope})/" if under_grad else f"/{scope}/"
        assert any(fwd in n for n in names), scope
        bwd = any(f"/transpose(jvp({scope}))/" in n for n in names)
        assert bwd == has_grad, scope


def test_eval_program_is_named():
    g = synthetic_tig("tiny", seed=0)
    stream = split_views(g).train
    batches, _ = build_batch_program(stream, CFG, np.random.default_rng(0))
    batches = {k: v for k, v in batches.items() if k != "labels"}
    tables = {k: jnp.asarray(v)
              for k, v in make_tables(g.edge_feat, g.node_feat).items()}
    fn = make_eval_epoch(CFG)
    text = fn.lower(init_params(jax.random.PRNGKey(0), CFG),
                    init_state(CFG, g.num_nodes), device_batches(batches),
                    tables).as_text()
    assert text.startswith("module @jit_scan_eval_stream ")


def _host_spans(logdir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name.startswith("tig.")}


def test_epoch_loop_spans_in_a_cpu_trace(tmp_path):
    g = synthetic_tig("tiny", seed=0)
    with jax.profiler.trace(str(tmp_path)):
        train_single(g, CFG, epochs=1)
    assert _host_spans(str(tmp_path)) >= {
        "tig.plan", "tig.stage", "tig.plan_wait", "tig.reset",
        "tig.dispatch", "tig.fetch"}
