"""Training checkpoint/resume: save mid-run, restore (including onto a
different mesh layout), and continue to identical losses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.models.config import tiny_test
from ome_tpu.parallel.mesh import MeshConfig, build_mesh
from ome_tpu.train import step as ts
from ome_tpu.train.checkpoint import (latest_step, restore_train_state,
                                      save_train_state)

pytest.importorskip("orbax.checkpoint")


def _setup(mesh_cfg):
    cfg = tiny_test().replace(num_layers=4)
    mesh = build_mesh(mesh_cfg, jax.devices()[:mesh_cfg.size])
    train_step, init_state = ts.make_train_step(cfg, mesh, mesh_cfg,
                                                num_microbatches=2)
    tokens = jnp.ones((4, 16), jnp.int32)
    targets = jnp.ones((4, 16), jnp.int32)
    sh = ts.data_sharding(mesh)
    return (mesh, train_step, init_state,
            jax.device_put(tokens, sh), jax.device_put(targets, sh))


def test_save_restore_resume_identical(tmp_path):
    mc = MeshConfig(dp=2, tp=2)
    mesh, train_step, init_state, tokens, targets = _setup(mc)
    with jax.set_mesh(mesh):
        params, opt = init_state(jax.random.PRNGKey(0))
        for step_i in range(2):
            params, opt, loss = train_step(params, opt, tokens, targets)
        save_train_state(str(tmp_path / "ckpt"), 2, params, opt)
        # continue the original run
        params, opt, loss_next = train_step(params, opt, tokens, targets)

        assert latest_step(str(tmp_path / "ckpt")) == 2
        p_like, o_like = init_state(jax.random.PRNGKey(1))
        step, params2, opt2 = restore_train_state(
            str(tmp_path / "ckpt"), p_like, o_like)
        assert step == 2
        params2, opt2, loss_resumed = train_step(params2, opt2, tokens,
                                                 targets)
    np.testing.assert_allclose(float(loss_resumed), float(loss_next),
                               rtol=1e-5)


def test_restore_onto_different_mesh(tmp_path):
    mc_a = MeshConfig(dp=4, tp=1)
    mesh, train_step, init_state, tokens, targets = _setup(mc_a)
    with jax.set_mesh(mesh):
        params, opt = init_state(jax.random.PRNGKey(0))
        params, opt, loss_a = train_step(params, opt, tokens, targets)
        save_train_state(str(tmp_path / "c"), 1, params, opt)

    mc_b = MeshConfig(dp=1, tp=2)
    mesh_b, train_step_b, init_state_b, tokens_b, targets_b = _setup(mc_b)
    with jax.set_mesh(mesh_b):
        p_like, o_like = init_state_b(jax.random.PRNGKey(1))
        _, params_b, opt_b = restore_train_state(str(tmp_path / "c"),
                                                 p_like, o_like)
        _, _, loss_b = train_step_b(params_b, opt_b, tokens_b, targets_b)
    # same state, different sharding: same next loss up to the
    # reduction-order jitter a different mesh layout introduces
    np.testing.assert_allclose(float(loss_b), float(
        _continue_once(mc_a, tmp_path)), rtol=5e-4)


def _continue_once(mc, tmp_path):
    mesh, train_step, init_state, tokens, targets = _setup(mc)
    with jax.set_mesh(mesh):
        p_like, o_like = init_state(jax.random.PRNGKey(2))
        _, params, opt = restore_train_state(str(tmp_path / "c"),
                                             p_like, o_like)
        _, _, loss = train_step(params, opt, tokens, targets)
    return loss
