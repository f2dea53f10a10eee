"""The deprecation ratchet: in-repo production flows must not route through
deprecated entry points.

The one-release shims (``encode_activation`` / ``decode_stream``) completed
their deprecation cycle and are now removed (docs/MIGRATION.md); this module
pins both halves of that promise: the representative end-to-end flows emit
no repo-owned DeprecationWarnings (so a future shim cannot silently creep
back into production paths), and the removed names really are gone.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.core.split import SplitInferenceEngine
from repro.data.synthetic import shapes_batch_iterator
from repro.models.cnn import init_cnn
from repro.serve import (ChannelConfig, MultiTenantGateway, OperatingPoint,
                         RateController, ServingGateway, SimulatedChannel,
                         TenantRequest, TenantSpec, build_rd_table)


@pytest.fixture(scope="module")
def tiny_system():
    cnn_cfg = smoke_config()._replace(input_size=32)
    data_cfg = smoke_data_config()._replace(image_size=32, batch_size=4)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {}
    for c in (4, 8):
        baf = init_baf_conv(jax.random.PRNGKey(c),
                            BaFConvConfig(c=c, q=cnn_cfg.split_q, hidden=8))
        bank[c] = (baf, np.arange(c))
    imgs, _ = next(shapes_batch_iterator(data_cfg, seed=11))
    return params, bank, np.asarray(imgs)


def _shim_deprecations(records):
    """DeprecationWarnings raised by this repo's own shims (their messages
    point at repro.pipeline); third-party deprecations are not ours to fix
    here and are ignored."""
    return [w for w in records
            if issubclass(w.category, DeprecationWarning)
            and "repro.pipeline" in str(w.message)]


def test_in_repo_serving_flows_emit_no_deprecation_warnings(tiny_system):
    params, bank, imgs = tiny_system
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # single-operating-point engine, end to end
        eng = SplitInferenceEngine(params, bank[8][0], np.arange(8), bits=6)
        eng(imgs[:2])
        # single-tenant gateway with a channel + controller
        table = build_rd_table(params, bank, imgs[:2], bits_sweep=(4, 8))
        gw = ServingGateway(
            params, bank,
            controller=RateController(table, quality_floor_db=0.0),
            channel=SimulatedChannel(ChannelConfig(bandwidth_bps=20e6)),
            max_batch=4)
        gw.serve(imgs)
        # multi-tenant event loop with rans wire accounting
        mt = MultiTenantGateway(
            params, bank, tenants=[TenantSpec("a"), TenantSpec("b")],
            default_op=OperatingPoint(c=8, bits=8),
            max_batch=4, batch_window_s=0.01, adaptive_window=True)
        mt.serve_tenants([
            TenantRequest("ab"[i % 2], imgs[i % len(imgs)], 0.001 * i)
            for i in range(6)])
    bad = _shim_deprecations(rec)
    assert not bad, (
        "in-repo flow still routes through deprecated entry points:\n"
        + "\n".join(f"{w.filename}:{w.lineno}: {w.message}" for w in bad))


def test_ratchet_filter_catches_repo_style_warnings():
    """Canary for the filter above: a repo-style deprecation (message
    pointing at repro.pipeline, as this repo's shims always did) must be
    caught, or the ratchet is silently blind. Any future shim MUST follow
    the same message convention for the ratchet to see it."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        warnings.warn("synthetic_shim is deprecated; use the "
                      "repro.pipeline plan API", DeprecationWarning)
        warnings.warn("unrelated third-party thing", DeprecationWarning)
    caught = _shim_deprecations(rec)
    assert len(caught) == 1
    assert "synthetic_shim" in str(caught[0].message)


def test_removed_shims_are_gone():
    """The one-release deprecation window closed: the loose-tuple entry
    points must no longer exist anywhere importable."""
    import repro.core.split as split
    for name in ("encode_activation", "decode_stream", "_decode_stream"):
        assert not hasattr(split, name), (
            f"core.split.{name} was promised removed after its one-release "
            f"deprecation window (docs/MIGRATION.md)")
