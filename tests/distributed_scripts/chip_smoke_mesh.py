"""chip_smoke.py's four-chip phase rehearsed on 4 fake CPU devices at the
reduced granite widths: fse_dp and ep, weights sharded over ``model``,
agree with the one-device capacity result."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import jax

import chip_smoke
from repro.configs import reduced_config

out = chip_smoke.mesh_phase(reduced_config(chip_smoke.ARCH), seed=0,
                            devices=jax.devices()[:4], seq=16)
assert set(out) == {"fse_dp", "ep"}, out
print("MESH PHASE OK")
