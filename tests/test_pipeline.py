"""GPipe pipeline parallelism over the pod axis: loss equivalence with the
plain forward + end-to-end differentiability.  Subprocess-isolated because
the 4-device host platform flag must precede jax init."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config
from repro.models import init_lm, lm_loss
from repro.distributed.pipeline import pipeline_loss, split_stage_params
from repro.distributed.sharding import rules_for
from repro.launch.mesh import make_mesh

cfg = dataclasses.replace(reduced_config("stablelm-3b"), n_layers=4)
params = init_lm(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
ref = float(lm_loss(params, toks, cfg, aux_weight=0.0)[0])
mesh = make_mesh((2, 2, 1), ("pod", "data", "model"))
rules = rules_for(cfg, mesh)
staged = split_stage_params(params, n_stages=2)
with mesh:
    loss = float(pipeline_loss(staged, toks, cfg, mesh, n_micro=2,
                               rules=rules))
    assert abs(loss - ref) / ref < 2e-2, (loss, ref)
    g = jax.grad(lambda p: pipeline_loss(p, toks, cfg, mesh, n_micro=2,
                                         rules=rules))(staged)
    gn = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
             for x in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
print("PIPELINE_OK", loss, ref)
"""


@pytest.mark.slow
def test_pipeline_loss_matches_and_differentiates():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PIPELINE_OK" in r.stdout
