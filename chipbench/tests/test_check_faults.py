"""With the timed path broken underneath, the check comes out false: a
token altered where it is produced, a step that returns its state
unchanged, half of the batch left out.  (A one-chip serve cell has no
exchange between chips to leave out.)"""
import pytest

from chipbench.tests import tiny

FAULTS = {
    # every decoded token altered where it is produced
    "token_altered": """
import jax.numpy as jnp
from repro.models import lm
_real = lm.decode_step
def decode_step(*a, **k):
    out = _real(*a, **k)
    return (jnp.roll(out[0], 1, axis=-1),) + tuple(out[1:])
lm.decode_step = decode_step
""",
    # half of the batch left out of the C3-SL superposition
    "half_batch_dropped": """
import jax.numpy as jnp
from repro.codecs import compose
_real = compose.Chain.encode
def encode(self, params, Z):
    B = Z.shape[-2]
    keep = (jnp.arange(B) % 2 == 0)[:, None]
    return _real(self, params, jnp.where(keep, Z, 0.0))
compose.Chain.encode = encode
""",
    # a decode step that returns its cache unchanged
    "state_unchanged": """
from repro.models import lm
_real = lm.decode_step
def decode_step(params, cache, *a, **k):
    out = _real(params, cache, *a, **k)
    return (out[0], cache) + tuple(out[2:])
lm.decode_step = decode_step
""",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(tmp_path, fault):
    res = tiny.run_in_child(tmp_path, seed=5, patch=FAULTS[fault])
    assert res["correct"] is False, res["checks"]
