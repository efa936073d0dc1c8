"""The offloaded train step names its parts with ``jax.named_scope`` (the
``SCOPE_*`` names in ``repro.core.offload``), so that a device trace can
tell the segments' forward, recompute and backward sweeps, the optimizer
and the model's head apart by each op's ``op_name`` metadata.  The scopes
are metadata alone: without them the compiled step is the same program."""
import contextlib
import re

import jax
import pytest

from repro.configs import SMOKE_SHAPE, get_config
from repro.configs.shapes import make_batch
from repro.core import offload as ofl
from repro.models import get_model
from repro.optim import adamw
from repro.train import init_train_state, make_train_step

ARCHS = ["mamba2-370m", "lstm-paper"]
MODEL_SCOPES = {
    # the model's own scopes, besides the segments' and the optimizer's
    "mamba2-370m": (ofl.SCOPE_PRELUDE, ofl.SCOPE_READOUT, ofl.SCOPE_SSD),
    "lstm-paper": (),
}
_META = re.compile(r', metadata=\{[^}]*\}')
# the tables of source locations that metadata's stack_frame_id points into
_FRAMES = re.compile(r'^(FileNames|FunctionNames|FileLocations|StackFrames)\n'
                     r'(.+\n)*', re.M)
_NAME = re.compile(r'%[\w.\-]+')


def _strip(hlo_text):
    """The compiled module's text without its debug metadata, and each
    instruction renamed by its first appearance: with the scopes a few
    fused broadcasts are numbered differently."""
    names = {}
    return _NAME.sub(lambda m: names.setdefault(m.group(), f"%v{len(names)}"),
                     _FRAMES.sub("", _META.sub("", hlo_text)))


def _compiled_text(arch):
    cfg = get_config(arch, smoke=True)
    model = get_model(cfg)
    opt = adamw(1e-3)
    step = make_train_step(model, opt, strategy="multistage_async",
                           engine="scan")
    state = jax.eval_shape(lambda k: init_train_state(model, opt, k),
                           jax.random.PRNGKey(0))
    batch = make_batch(cfg, SMOKE_SHAPE)
    return jax.jit(step).lower(state, batch).compile().as_text()


def _scopes(op_name):
    return {s for s in re.split(r"[/()]", op_name) if s}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """The persistent cache's key leaves op metadata out, so a step loaded
    from it would carry the metadata of whichever compile filled it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def scoped():
    return {}


def _scoped_text(scoped, arch):
    if arch not in scoped:
        scoped[arch] = _compiled_text(arch)
    return scoped[arch]


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step_fn)/jvp(chain.segment)/while/body/mul", "forward"),
    ("jit(step_fn)/transpose(jvp(chain.segment))/while/body/checkpoint/"
     "rematted_computation/chain.segment/mul", "recompute"),
    ("jit(step_fn)/transpose(jvp(chain.segment))/while/body/mul",
     "backward"),
])
def test_phase_of_reads_jax_markers(op_name, phase):
    assert ofl.phase_of(op_name) == phase


@pytest.mark.parametrize("arch", ARCHS)
def test_step_metadata_names_every_part(scoped, arch):
    names = re.findall(r'op_name="([^"]*)"', _scoped_text(scoped, arch))
    seen = {(s, ofl.phase_of(n)) for n in names for s in _scopes(n)}
    for phase in ("forward", "recompute", "backward"):
        assert (ofl.SCOPE_SEGMENT, phase) in seen, phase
    assert (ofl.SCOPE_OPTIMIZER, "forward") in seen
    for scope in MODEL_SCOPES[arch]:
        assert any(s == scope for s, _ in seen), scope
    if arch == "mamba2-370m":
        # the SSD scan runs inside the segments, in all three sweeps
        for phase in ("forward", "recompute", "backward"):
            assert any(ofl.SCOPE_SSD in _scopes(n)
                       and ofl.SCOPE_SEGMENT in _scopes(n)
                       and ofl.phase_of(n) == phase for n in names), phase


@pytest.mark.parametrize("arch", ARCHS)
def test_scopes_change_metadata_only(scoped, arch, monkeypatch):
    with_scopes = _scoped_text(scoped, arch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _compiled_text(arch)
    assert ofl.SCOPE_SEGMENT not in without
    assert ofl.SCOPE_SEGMENT in with_scopes
    assert _strip(with_scopes) == _strip(without)
