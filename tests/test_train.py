"""Tests: optimizer, train step, checkpointing (+elastic), compression, data."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.data.synthetic import DataConfig, batch_at, for_model
from repro.data.packing import pack_documents, packing_efficiency
from repro.launch.mesh import AxisType, make_mesh
from repro.train import checkpoint as ckpt
from repro.train.compress import (compress_roundtrip, ef_compress,
                                  init_error_state)
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state, lr_at
from repro.train.step import make_train_step, param_specs, shardings_for


def tiny_mesh():
    return make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))


class TestOptimizer:
    def test_adamw_minimizes_quadratic(self):
        params = {"w": jnp.array([5.0, -3.0])}
        opt = init_opt_state(params)
        cfg = OptConfig(lr=0.2, warmup_steps=0, total_steps=200,
                        weight_decay=0.0, clip_norm=10.0)
        loss = lambda p: jnp.sum(jnp.square(p["w"]))
        for _ in range(150):
            g = jax.grad(loss)(params)
            params, opt, _ = adamw_update(params, g, opt, cfg)
        assert float(loss(params)) < 1e-2

    def test_lr_schedule(self):
        cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                        min_lr_frac=0.1)
        assert float(lr_at(cfg, jnp.int32(5))) == pytest.approx(0.5)
        assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0, rel=0.1)
        assert float(lr_at(cfg, jnp.int32(100))) == pytest.approx(0.1,
                                                                  rel=0.01)

    def test_grad_clipping_bounds_update(self):
        params = {"w": jnp.zeros((4,))}
        opt = init_opt_state(params)
        cfg = OptConfig(lr=0.1, warmup_steps=0, clip_norm=1.0,
                        weight_decay=0.0)
        g = {"w": jnp.full((4,), 1e6)}
        _, _, metrics = adamw_update(params, g, opt, cfg)
        assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip


class TestTrainStep:
    def test_loss_decreases_with_microbatching(self):
        cfg = get_config("qwen15_05b").reduced()
        mesh = tiny_mesh()
        params, _ = init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        step, psh, osh = make_train_step(
            cfg, OptConfig(warmup_steps=2, total_steps=50), mesh,
            num_microbatches=2, dtype=jnp.float32)
        params = jax.device_put(params, psh)
        opt = jax.device_put(opt, osh)
        dcfg = for_model(cfg, seq_len=32, global_batch=4)
        losses = []
        for i in range(8):
            params, opt, m = step(params, opt, batch_at(dcfg, i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_microbatched_grads_match_full_batch(self):
        from repro.train.step import loss_and_grads
        cfg = get_config("qwen15_05b").reduced()
        params, _ = init_params(cfg, jax.random.PRNGKey(1))
        batch = batch_at(for_model(cfg, seq_len=16, global_batch=4), 0)
        l1, _, g1 = loss_and_grads(params, cfg, batch, 1, jnp.float32)
        l2, _, g2 = loss_and_grads(params, cfg, batch, 4, jnp.float32)
        # microbatch losses are per-microbatch token means; close but not
        # identical when mask counts differ -> compare loosely, grads tight
        # after normalizing by the same convention.
        np.testing.assert_allclose(float(l1), float(l2), rtol=0.05)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.1, atol=2e-2)

    def test_moe_arch_trains(self):
        cfg = get_config("olmoe_1b_7b").reduced()
        mesh = tiny_mesh()
        params, _ = init_params(cfg, jax.random.PRNGKey(2))
        opt = init_opt_state(params)
        step, psh, osh = make_train_step(
            cfg, OptConfig(warmup_steps=1, total_steps=20), mesh,
            dtype=jnp.float32)
        params = jax.device_put(params, psh)
        opt = jax.device_put(opt, osh)
        dcfg = for_model(cfg, seq_len=16, global_batch=2)
        for i in range(3):
            params, opt, m = step(params, opt, batch_at(dcfg, i))
            assert np.isfinite(float(m["loss"]))


class TestCheckpoint:
    def _setup(self, tmp_path):
        cfg = get_config("qwen15_05b").reduced()
        params, _ = init_params(cfg, jax.random.PRNGKey(3))
        opt = init_opt_state(params)
        return cfg, params, opt, str(tmp_path / "ckpt")

    def test_roundtrip(self, tmp_path):
        cfg, params, opt, d = self._setup(tmp_path)
        ckpt.save(d, 7, params, opt, extra={"arch": cfg.name})
        assert ckpt.latest_step(d) == 7
        p2, o2, meta = ckpt.restore(d, 7, params, opt)
        assert meta["arch"] == cfg.name
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_keep_k_gc_and_latest(self, tmp_path):
        cfg, params, opt, d = self._setup(tmp_path)
        for s in (1, 2, 3, 4, 5):
            ckpt.save(d, s, params, opt, keep=2)
        assert sorted(ckpt.all_steps(d)) == [4, 5]
        assert ckpt.latest_step(d) == 5

    def test_partial_save_is_invisible(self, tmp_path):
        """A checkpoint dir without committed rename must be ignored —
        models the node-died-mid-save failure."""
        cfg, params, opt, d = self._setup(tmp_path)
        ckpt.save(d, 1, params, opt)
        os.makedirs(os.path.join(d, "tmp.2"))  # simulated dead partial save
        assert ckpt.latest_step(d) == 1

    def test_elastic_resharding(self, tmp_path):
        """Save from a (1,1) mesh; restore onto a different mesh layout —
        the elastic-scaling path."""
        cfg, params, opt, d = self._setup(tmp_path)
        ckpt.save(d, 3, params, opt)
        mesh2 = make_mesh((1,), ("model",),  # different topology
                          axis_types=(AxisType.Auto,))
        psh = shardings_for(mesh2, param_specs(cfg))
        p2, _, _ = ckpt.restore(d, 3, params, opt, param_sh=psh)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_save(self, tmp_path):
        cfg, params, opt, d = self._setup(tmp_path)
        t = ckpt.save(d, 9, params, opt, async_save=True)
        t.join(timeout=60)
        assert ckpt.latest_step(d) == 9


class TestCompression:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal(10_000).astype(np.float32))
        out = compress_roundtrip(g)
        err = float(jnp.max(jnp.abs(out - g)))
        assert err <= float(jnp.max(jnp.abs(g))) / 127.0 + 1e-6

    def test_error_feedback_accumulates(self):
        """EF: the sum of compressed sends converges to the sum of grads."""
        rng = np.random.default_rng(1)
        grads = {"w": jnp.asarray(rng.standard_normal(512)
                                  .astype(np.float32))}
        e = init_error_state(grads)
        sent_total = jnp.zeros(512)
        for _ in range(30):
            sent, e = ef_compress(grads, e)
            sent_total = sent_total + sent["w"]
        target = 30 * grads["w"]
        resid = float(jnp.max(jnp.abs(sent_total - target)))
        assert resid <= float(jnp.max(jnp.abs(grads["w"]))) / 127.0 + 1e-5


class TestData:
    def test_deterministic_across_restart(self):
        dcfg = DataConfig(seed=11, vocab_size=1000, seq_len=64,
                          global_batch=4)
        b1 = batch_at(dcfg, 42)
        b2 = batch_at(dcfg, 42)  # "restarted host" recomputes
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        b3 = batch_at(dcfg, 43)
        assert not np.array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b3["tokens"]))

    def test_balanced_packing(self):
        rng = np.random.default_rng(2)
        lens = (rng.pareto(1.2, 200) * 50 + 1).astype(np.int64)
        starts, _ = pack_documents(jnp.asarray(lens), 16)
        per_row = np.diff(np.asarray(starts))
        assert per_row.max() - per_row.min() <= per_row.mean() * 0.1 + 16
        stats = packing_efficiency(lens, 16)
        assert stats["balanced_efficiency"] > stats["naive_efficiency"]
        assert stats["balanced_efficiency"] > 0.9
