"""Substrate tests: data determinism, checkpoint/resume, fault tolerance,
elastic planning, optimizer behaviour, serving KV tiering."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, save_checkpoint, load_checkpoint
from repro.checkpoint.store import latest_step
from repro.data import SyntheticLMDataset
from repro.optim import adamw, cosine_schedule, global_norm
from repro.runtime.fault_tolerance import StepWatchdog, StragglerMonitor, retry_step
from repro.runtime.elastic import plan_mesh
from repro.runtime.fault_tolerance import StepTimeoutError


class TestData:
    def test_deterministic_across_restarts(self):
        ds = SyntheticLMDataset(1000, 32, 8, seed=3)
        b1 = ds.batch_at(17)
        b2 = ds.batch_at(17)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_labels_shifted(self):
        ds = SyntheticLMDataset(1000, 32, 4)
        b = ds.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_host_slices_partition_global_batch(self):
        ds = SyntheticLMDataset(1000, 16, 8)
        full = ds.batch_at(5)["tokens"]
        parts = [
            ds.batch_at(5, lo=i * 2, hi=(i + 1) * 2)["tokens"] for i in range(4)
        ]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_different_steps_differ(self):
        ds = SyntheticLMDataset(1000, 16, 4)
        assert not np.array_equal(
            ds.batch_at(0)["tokens"], ds.batch_at(1)["tokens"]
        )


class TestCheckpoint:
    def test_roundtrip_and_commit(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        save_checkpoint(tmp_path, 7, tree)
        assert latest_step(tmp_path) == 7
        out, manifest = load_checkpoint(tmp_path, 7, tree)
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert manifest["step"] == 7

    def test_corruption_detected(self, tmp_path):
        tree = {"a": jnp.ones(8)}
        tgt = save_checkpoint(tmp_path, 1, tree)
        npy = next(p for p in tgt.glob("*.npy"))
        arr = np.load(npy)
        arr[0] = 999.0
        np.save(npy, arr)
        with pytest.raises(IOError):
            load_checkpoint(tmp_path, 1, tree)

    def test_manager_retention_and_resume(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
        for s in (10, 20, 30):
            mgr.save(s, {"x": jnp.full(3, float(s))})
        steps = sorted(
            int(p.name.split("_")[1]) for p in tmp_path.glob("step_*")
        )
        assert steps == [20, 30]
        restored, manifest = mgr.restore_latest({"x": jnp.zeros(3)})
        assert manifest["step"] == 30
        np.testing.assert_array_equal(restored["x"], np.full(3, 30.0))

    def test_async_save_completes(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2, async_save=True)
        mgr.save(5, {"x": jnp.ones(2)})
        mgr.wait()
        assert latest_step(tmp_path) == 5


class TestFaultTolerance:
    def test_retry_recovers_transient(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return "ok"

        assert retry_step(flaky, retries=3, backoff_s=0.0) == "ok"
        assert calls["n"] == 3

    def test_retry_exhausts(self):
        def dead():
            raise RuntimeError("permanent")

        with pytest.raises(RuntimeError):
            retry_step(dead, retries=1, backoff_s=0.0)

    def test_watchdog_fires(self):
        import time

        with pytest.raises(StepTimeoutError):
            with StepWatchdog(timeout_s=0.05):
                time.sleep(0.2)

    def test_watchdog_passes_fast_step(self):
        with StepWatchdog(timeout_s=5.0):
            pass

    def test_straggler_flagged(self):
        mon = StragglerMonitor(patience=2)
        flagged = []
        for _ in range(3):
            flagged = mon.observe(
                {f"h{i}": 1.0 for i in range(8)} | {"slow": 3.0}
            )
        assert flagged == ["slow"]


class TestElastic:
    def test_plan_shrinks_data_axis(self):
        # 224 devices / TP16 -> 14 replicas, but 256 batch needs a divisor:
        # the plan drops to 8 replicas and parks the rest
        p = plan_mesh(n_devices=224, model_parallel=16, global_batch=256)
        assert p.model == 16
        assert p.data == 8
        assert p.dropped_devices == 224 - 8 * 16
        assert p.data * p.per_replica_batch == 256

    def test_plan_exact_fit(self):
        p = plan_mesh(n_devices=256, model_parallel=16, global_batch=256)
        assert (p.data, p.dropped_devices) == (16, 0)

    def test_plan_respects_batch_divisibility(self):
        p = plan_mesh(n_devices=240, model_parallel=16, global_batch=256)
        assert 256 % p.data == 0

    def test_plan_rejects_too_few(self):
        with pytest.raises(ValueError):
            plan_mesh(n_devices=8, model_parallel=16, global_batch=64)


class TestOptimizer:
    def test_adamw_descends_quadratic(self):
        opt = adamw(lr=0.1, weight_decay=0.0, clip_norm=None)
        params = {"w": jnp.array([3.0, -2.0])}
        state = opt.init(params)
        for _ in range(60):
            grads = {"w": 2 * params["w"]}
            params, state = opt.update(grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.2

    def test_clip_norm(self):
        opt = adamw(lr=0.0, clip_norm=1.0)
        params = {"w": jnp.zeros(3)}
        state = opt.init(params)
        _, state = opt.update({"w": jnp.full(3, 100.0)}, state, params)
        assert float(global_norm(state["m"])) <= 0.12  # (1-b1)*clipped

    def test_cosine_schedule_shape(self):
        lr = cosine_schedule(1.0, warmup=10, total=100)
        assert float(lr(0)) == 0.0
        assert float(lr(10)) == pytest.approx(1.0, abs=0.02)
        assert float(lr(100)) == pytest.approx(0.0, abs=1e-3)

    def test_bf16_state_dtype(self):
        opt = adamw(lr=0.1, state_dtype=jnp.bfloat16)
        params = {"w": jnp.ones(4)}
        state = opt.init(params)
        assert state["m"]["w"].dtype == jnp.bfloat16


class TestTieredServing:
    def _mk(self, hbm=64, total=256):
        from repro.serving import ContinuousBatcher, TieredPagedKV, TieredServer
        from repro.serving.kv_cache import KVPageConfig

        kv = TieredPagedKV(
            KVPageConfig(n_groups=2, page_size=4, kv_heads=2, head_dim=8),
            total_pages=total,
            hbm_capacity=hbm,
        )
        batcher = ContinuousBatcher(
            n_sessions=40, page_size=4, max_batch=8, seed=1
        )
        return kv, batcher, TieredServer(kv, batcher)

    def test_pages_migrate_and_data_survives(self):
        kv, _, _ = self._mk()
        kv.ensure_resident(np.array([5]))
        data = jnp.arange(kv.cfg.elems_per_page, dtype=jnp.bfloat16)
        kv.write_tokens(np.array([5]), data[None])
        kv.demote(np.array([5]))
        assert kv.tier_of(5).name == "SLOW"
        kv.ensure_resident(np.array([5]))
        got = kv.hbm[int(kv.hbm_slot[5])]
        np.testing.assert_array_equal(
            np.asarray(got, np.float32).reshape(-1), np.asarray(data, np.float32)
        )

    def test_server_rounds_and_watermark(self):
        kv, batcher, server = self._mk()
        kv.pool.set_fm_size(48)
        server.run(rounds=60, drift_every=0)
        s = server.summary()
        assert s["rounds"] == 60
        assert s["migrated_in"] > 0
        # HBM occupancy respects the watermark-set budget
        assert kv.pool.fast_used <= 64


class TestCompileCachePath:
    def test_env_wins_else_fixed_checkout_path(self, monkeypatch, tmp_path):
        from repro.runtime.compile_cache import ENV, compile_cache_dir

        monkeypatch.setenv(ENV, str(tmp_path / "from_env"))
        assert compile_cache_dir(tmp_path) == (str(tmp_path / "from_env"), True)
        monkeypatch.delenv(ENV)
        path, from_env = compile_cache_dir(tmp_path)
        assert not from_env
        assert path == str(tmp_path.resolve() / ".jax_cache")
        # the same checkout always maps to the same directory: the path is
        # part of the cache key, so it must never vary between runs
        assert compile_cache_dir(tmp_path) == (path, False)
