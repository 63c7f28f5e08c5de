"""compat/jaxshim — the one sanctioned JAX version boundary.

``jax_version`` re-reads ``jax.__version__`` per call (never cached at
import) so a wrapper that has to differ between releases can be
tested against a mocked one. None does today: ``shard_map`` and
``axis_size`` call the installed release's spelling directly.
"""

import numpy as np
import pytest

import jax

from horovod_tpu.compat import jaxshim

pytestmark = pytest.mark.fast


# -- version parsing --------------------------------------------------------

@pytest.mark.parametrize("raw,want", [
    ("0.4.37", (0, 4, 37)),
    ("0.5.0", (0, 5, 0)),
    ("0.7.0.dev20260101+abc123", (0, 7, 0)),
    ("0.6", (0, 6)),
    ("1.0.0rc1", (1, 0, 0)),
    ("garbage", (0,)),
])
def test_parse_version(raw, want):
    assert jaxshim._parse_version(raw) == want


def test_jax_version_reads_live_not_cached(monkeypatch):
    monkeypatch.setattr(jax, "__version__", "0.9.9")
    assert jaxshim.jax_version() == (0, 9, 9)
    monkeypatch.setattr(jax, "__version__", "0.4.37")
    assert jaxshim.jax_version() == (0, 4, 37)


# -- shard_map ---------------------------------------------------------------

def test_shard_map_takes_top_level_check_vma(monkeypatch):
    """The shim calls the top-level ``jax.shard_map`` with the
    ``check_vma`` spelling and the checker off."""
    seen = {}

    def fake_shard_map(body, mesh=None, in_specs=None, out_specs=None,
                       **kw):
        seen.update(kw, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, body=body)
        return "future-mapped"

    monkeypatch.setattr(jax, "shard_map", fake_shard_map)
    out = jaxshim.shard_map(lambda x: x, mesh="M", in_specs="I",
                            out_specs="O")
    assert out == "future-mapped"
    assert seen["mesh"] == "M" and seen["in_specs"] == "I" \
        and seen["out_specs"] == "O"
    assert seen["check_vma"] is False and "check_rep" not in seen


def test_shard_map_executes_on_running_jax():
    """The spelling the shim calls must actually trace on the
    INSTALLED jax: one psum over a real mesh (conftest forces an
    8-device host platform)."""
    mesh = jaxshim.make_mesh()
    n = mesh.devices.size
    spec = jaxshim.partition_spec("data")

    def body(x):
        return jax.lax.psum(x, "data")

    y = jax.jit(jaxshim.shard_map(body, mesh=mesh, in_specs=spec,
                                  out_specs=spec))(
        np.arange(n, dtype=np.float32))
    np.testing.assert_allclose(
        np.asarray(y), np.full(n, np.arange(n).sum(), np.float32))


# -- axis_size --------------------------------------------------------------

def test_axis_size_calls_lax_axis_size(monkeypatch):
    monkeypatch.setattr(jax.lax, "axis_size", lambda a: 7)
    assert jaxshim.axis_size("model") == 7


# -- mesh construction ------------------------------------------------------

def test_make_mesh_default_is_one_data_axis():
    mesh = jaxshim.make_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == len(jax.devices())


def test_make_mesh_infers_minus_one_axis():
    mesh = jaxshim.make_mesh({"data": -1, "model": 1})
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == len(jax.devices())


def test_make_mesh_rejects_bad_product():
    with pytest.raises(ValueError, match="devices"):
        jaxshim.make_mesh({"data": len(jax.devices()) + 1})
    with pytest.raises(ValueError, match="-1"):
        jaxshim.make_mesh({"a": -1, "b": -1})


def test_named_sharding_coerces_specs():
    mesh = jaxshim.make_mesh()
    for spec in ("data", ("data", None),
                 jaxshim.partition_spec("data")):
        s = jaxshim.named_sharding(mesh, spec)
        assert s.spec[0] == "data"
