"""The emulated GEMM's stages carry ``jax.named_scope`` names into the
compiled HLO's op_name metadata (what a device trace's ops are tagged
with), on the core route and on the fused Pallas route (interpreted)."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import ozmm
from repro.kernels.fused import ozmm_pallas_fused

STAGES = {"ozmm.scaling", "ozmm.quantize", "ozmm.products", "ozmm.reconstruct"}

ROUTES = {
    "core": lambda a, b: ozmm(a, b, "ozaki2-fp8/accurate@8"),
    "fused": lambda a, b: ozmm_pallas_fused(a, b, num_moduli=8, interpret=True,
                                            reconstruct="xla"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_stage_scopes_in_op_names(route):
    a = jax.ShapeDtypeStruct((32, 24), jnp.float64)
    b = jax.ShapeDtypeStruct((24, 16), jnp.float64)
    hlo = jax.jit(ROUTES[route]).lower(a, b).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = {s for n in op_names for s in re.findall(r"ozmm\.[a-z]+", n)}
    assert scopes >= STAGES
    if route == "core":  # Garner runs in XLA here, inside the fused kernel there
        assert any("ozmm.reconstruct/ozmm.garner/" in n for n in op_names)
