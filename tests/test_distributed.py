"""Distribution tests.

Multi-device behaviour needs XLA host-device-count set before jax init, so
those cases run in subprocesses; in-process tests cover the pure helpers
(input specs, sharding rules).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import ARCHS, SHAPES, get_config, input_specs

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8, timeout: int = 500):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# Pure helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    specs = input_specs(cfg, shape)
    assert "tokens" in specs
    assert specs["tokens"].shape[0] == cell.global_batch
    if cfg.n_patches and cell.phase != "decode":
        total = specs["tokens"].shape[1] + cfg.n_patches
        assert total == cell.seq_len
    if cell.phase == "train":
        assert "labels" in specs


def test_param_shardings_match_tree():
    from repro.models.lm_common import init_params, param_shardings

    for arch in ARCHS:
        cfg = get_config(arch)
        sds = jax.eval_shape(lambda c=cfg: init_params(c, jax.random.PRNGKey(0)))
        spec = param_shardings(cfg)
        assert jax.tree.structure(sds) == jax.tree.structure(
            spec, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
        )


# ---------------------------------------------------------------------------
# Multi-device subprocess tests
# ---------------------------------------------------------------------------


def test_pipeline_runner_matches_sequential():
    _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import generate_seed, paper_platform
        from repro.models.cnn import make_cnn, network_layers, canonical_pipeline_apply
        from repro.launch.mesh import make_stage_mesh
        from repro.pipeline import PipelineRunner

        model = make_cnn("synthnet", scale=0.1)
        params = model.init(jax.random.PRNGKey(0))
        seed = generate_seed([l.weight for l in network_layers("synthnet")], paper_platform(4), n_stages=4)
        in_shape = (8, 8, 8)
        apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, in_shape)
        runner = PipelineRunner(mesh=make_stage_mesh(4), conf=seed.conf, apply_layer=apply_fn, n_micro=5)
        micro_raw = jax.random.normal(jax.random.PRNGKey(1), (5, 2) + in_shape)
        out = crop_out(runner.run(jax.vmap(to_canon)(micro_raw)))
        ref = jnp.stack([model(params, micro_raw[i]) for i in range(5)])
        assert np.allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4), "pipeline != sequential"
        print("OK")
        """,
        devices=4,
    )


def test_tiny_mesh_train_step_with_moe():
    """MoE shard_map path under pjit on a real (4-device) mesh."""
    _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models.lm_common import init_params, param_shardings
        from repro.models.transformer import make_train_step
        from repro.optim import AdamW, AdamWConfig

        cfg = get_smoke("phi3.5-moe-42b")
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = AdamW(AdamWConfig(total_steps=4, warmup=1))
        state = opt.init(params)
        pspec = param_shardings(cfg)
        ospec = {"step": P(), "mu": pspec, "nu": pspec, "master": pspec}
        bspec = {"tokens": P(("data",), None), "labels": P(("data",), None)}
        step = make_train_step(cfg, opt, mesh, ("data",), "model")
        jstep = jax.jit(step,
            in_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), (pspec, ospec, bspec),
                                      is_leaf=lambda x: isinstance(x, P)),
        )
        batch = {"tokens": jnp.zeros((4, 16), jnp.int32), "labels": jnp.zeros((4, 16), jnp.int32)}
        with mesh:
            p, o, m = jstep(params, state, batch)
        assert jnp.isfinite(m["loss"]), m
        print("OK", float(m["loss"]))
        """,
        devices=4,
    )


def test_moe_local_vs_sharded_equivalence():
    """shard_map MoE == local MoE when TP=1 (same dispatch per data shard)."""
    _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models import blocks
        from repro.models.lm_common import init_params
        import dataclasses

        cfg = dataclasses.replace(get_smoke("phi3.5-moe-42b"), dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["blocks"])
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        y_local, _ = blocks.moe_ffn(cfg, lp, x, None)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
        with mesh:
            y_shard, _ = jax.jit(lambda xx: blocks.moe_ffn(cfg, lp, xx, mesh, ("data",), "model"))(x)
        assert np.allclose(np.asarray(y_local), np.asarray(y_shard), rtol=1e-4, atol=1e-4)
        print("OK")
        """,
        devices=2,
    )

