"""Multi-device semantics via subprocesses (8 fake CPU devices).

The main test process keeps 1 device by design (see conftest); these
tests spawn `python -c` with XLA_FLAGS to get an 8-device host, then
assert sharded-vs-single-device numerical equivalence and collective
behavior (incl. the int8 error-feedback gradient compression) — and,
for the serving engine, tensor-parallel token parity plus the
no-resharding contract on the fused decode tick.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, forbid_stderr: tuple = ()) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    for marker in forbid_stderr:
        assert marker not in out.stderr, (
            f"forbidden stderr marker {marker!r}:\n" + out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestShardedTraining:
    def test_sharded_loss_matches_single_device(self):
        res = run_py("""
            import json, jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.models import api
            from repro.launch.steps import make_train_step, TrainHParams
            from repro.optim import adamw_init
            from repro.runtime import sharding as shr

            cfg = configs.get_smoke("tinyllama-1.1b", d_model=64, n_heads=4,
                                    n_kv_heads=2, vocab=256)
            params = api.init(cfg, jax.random.key(0))
            opt = adamw_init(params)
            r = np.random.RandomState(0)
            batch = {"tokens": jnp.asarray(r.randint(0, 256, (8, 32)), jnp.int32),
                     "labels": jnp.asarray(r.randint(0, 256, (8, 32)), jnp.int32)}
            hp = TrainHParams(peak_lr=1e-3, warmup=1, total=10)

            # single-logical-device result
            p1, o1, m1 = jax.jit(make_train_step(cfg, hp))(params, opt, batch)

            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((2, 4))
            psh = shr.tree_shardings(mesh, jax.eval_shape(lambda: params))
            osh = shr.tree_shardings(mesh, jax.eval_shape(lambda: opt))
            bsh = shr.batch_shardings(mesh, cfg, jax.eval_shape(lambda: batch), 8)
            dp = shr.dp_axes(mesh, 8)
            step = jax.jit(make_train_step(cfg, hp, mesh=mesh, dp=dp),
                           in_shardings=(psh, osh, bsh))
            p2, o2, m2 = step(params, opt, batch)
            dmax = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                             b.astype(jnp.float32))))
                       for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
            print(json.dumps({"loss1": float(m1["loss"]),
                              "loss2": float(m2["loss"]), "dparam": dmax}))
        """)
        assert abs(res["loss1"] - res["loss2"]) < 5e-3
        assert res["dparam"] < 5e-3

    def test_compressed_pod_mean_close_to_exact(self):
        res = run_py("""
            import json, jax, jax.numpy as jnp, numpy as np
            from repro.optim.compression import compressed_grad_fn, ef_init

            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
            def loss_fn(p, batch):
                x, y = batch["x"], batch["y"]
                pred = x @ p["w"]
                return jnp.mean((pred - y) ** 2)
            r = np.random.RandomState(0)
            p = {"w": jnp.asarray(r.randn(16, 4), jnp.float32)}
            batch = {"x": jnp.asarray(r.randn(8, 16), jnp.float32),
                     "y": jnp.asarray(r.randn(8, 4), jnp.float32)}
            exact = jax.grad(lambda pp: loss_fn(pp, batch))(p)
            fn = compressed_grad_fn(loss_fn, mesh, axis="pod")
            with mesh:
                loss, g, ef = jax.jit(fn)(p, batch, ef_init(p))
            rel = float(jnp.linalg.norm(g["w"] - exact["w"]) /
                        jnp.linalg.norm(exact["w"]))
            efn = float(jnp.linalg.norm(ef["w"]))
            print(json.dumps({"rel": rel, "ef_norm": efn,
                              "loss": float(loss)}))
        """)
        # int8 quantization: ~1% relative error on the mean, residual kept
        assert res["rel"] < 0.02
        assert res["ef_norm"] > 0  # feedback captured the residual

    def test_elastic_restore_onto_different_mesh(self):
        res = run_py("""
            import json, tempfile, jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.models import api
            from repro.checkpoint import save_checkpoint, load_checkpoint
            from repro.runtime import sharding as shr

            cfg = configs.get_smoke("tinyllama-1.1b", d_model=64, n_heads=4,
                                    n_kv_heads=2, vocab=256)
            params = api.init(cfg, jax.random.key(1))
            d = tempfile.mkdtemp()
            path = save_checkpoint(d, 3, params)

            # restore onto a DIFFERENT mesh shape (elastic path)
            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((4, 2))
            sh = shr.tree_shardings(mesh, jax.eval_shape(lambda: params))
            restored, manifest = load_checkpoint(
                path, jax.eval_shape(lambda: params), shardings=sh)
            ok = all(bool(jnp.all(a == b)) for a, b in
                     zip(jax.tree.leaves(params), jax.tree.leaves(restored)))
            sharded = any(len(l.sharding.device_set) > 1
                          for l in jax.tree.leaves(restored))
            print(json.dumps({"equal": ok, "sharded": sharded,
                              "step": manifest["step"]}))
        """)
        assert res["equal"] and res["sharded"] and res["step"] == 3


@pytest.mark.slow
class TestShardedServing:
    def test_sharded_serving_token_parity_and_no_resharding(self):
        """The tensor-parallel engine on a (2, 4) mesh over 8 forced host
        devices must be token-for-token identical to the single-device
        engine (greedy fp32), and the compiled decode tick must carry the
        pool's cache shardings through unchanged (no resharding at the
        donation boundary; no involuntary remat inside — the partitioner
        logs the latter to stderr, which run_py screens)."""
        res = run_py("""
            import json, jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.models import api
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import Engine, EngineConfig, Request

            cfg = configs.get_smoke("tinyllama-1.1b", dtype="float32",
                                    param_dtype="float32")
            params = api.init(cfg, jax.random.key(0))
            rng = np.random.RandomState(0)
            specs = [(6, 5, 0.0), (9, 8, 0.0), (4, 3, 0.02), (7, 6, 0.03),
                     (5, 4, 0.04)]
            reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                            max_new_tokens=g, arrival_time=t)
                    for i, (s, g, t) in enumerate(specs)]

            e1 = Engine(cfg, params, EngineConfig(n_slots=2))
            o1, _ = e1.run(reqs)
            mesh = make_serving_mesh("2x4")
            e2 = Engine(cfg, params, EngineConfig(n_slots=2), mesh=mesh)
            o2, m2 = e2.run(reqs)
            parity = all(np.array_equal(o1[r.rid].tokens, o2[r.rid].tokens)
                         for r in reqs)

            # params + pool actually sharded (not silently replicated)
            sharded_params = sum(
                len(l.sharding.device_set) > 1
                for l in jax.tree.leaves(e2.params))
            pool_sh = e2._cache_sh
            sharded_cache = sum(
                s.spec != jax.sharding.PartitionSpec()
                for s in jax.tree.leaves(pool_sh))

            # no-resharding lowering check: compile the greedy tick with
            # the pool shardings and compare cache in/out shardings
            cache = jax.device_put(
                api.make_cache(cfg, 2, e2.s_max, jnp.float32), pool_sh)
            args = (e2.params, cache, jnp.zeros(2, jnp.int32),
                    jnp.zeros((2, 1), jnp.int32), jnp.zeros(2, jnp.float32),
                    jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                    e2._key)
            compiled = e2._tick_fn(False).lower(*args).compile()
            n = len(jax.tree.leaves(cache))
            flat_in = jax.tree.leaves(compiled.input_shardings[0])
            in_cache = flat_in[len(jax.tree.leaves(e2.params)):][:n]
            out_cache = jax.tree.leaves(compiled.output_shardings)[-n:]
            leaves = jax.tree.leaves(cache)
            no_reshard = all(
                a.is_equivalent_to(b, l.ndim) and
                a.is_equivalent_to(s, l.ndim)
                for a, b, s, l in zip(in_cache, out_cache,
                                      jax.tree.leaves(pool_sh), leaves))

            print(json.dumps({
                "parity": parity,
                "ticks": m2.decode_ticks,
                "sharded_params": sharded_params,
                "sharded_cache": sharded_cache,
                "no_reshard": no_reshard,
            }))
        """, forbid_stderr=("Involuntary full rematerialization",))
        assert res["parity"], "sharded vs single-device token mismatch"
        assert res["ticks"] > 0
        assert res["sharded_params"] > 0
        assert res["sharded_cache"] > 0
        assert res["no_reshard"], "decode tick resharded the cache"

    def test_sharded_paged_pool_token_parity(self):
        """The paged engine (block-table arena, prefix sharing on) over a
        (2, 4) mesh must match the single-device slot-pool engine token
        for token — the rank-5 k/v rule shards the page arena the same
        way it shards slot rows (page axis in the slot position), and
        the gathered block-table indexing must commute with the 'model'
        head sharding."""
        res = run_py("""
            import json, jax, numpy as np
            from repro import configs
            from repro.models import api
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import Engine, EngineConfig, Request

            cfg = configs.get_smoke("tinyllama-1.1b", dtype="float32",
                                    param_dtype="float32")
            params = api.init(cfg, jax.random.key(3))
            rng = np.random.RandomState(3)
            shared = rng.randint(0, cfg.vocab, (8,))
            reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (5+i,)),
                            max_new_tokens=4 + i) for i in range(3)]
            # plus two sharers of one prompt: prefix reuse under TP
            reqs += [Request(rid=10 + i, prompt=shared, max_new_tokens=4)
                     for i in range(2)]

            e1 = Engine(cfg, params, EngineConfig(n_slots=2))
            o1, _ = e1.run(reqs)
            e2 = Engine(cfg, params,
                        EngineConfig(n_slots=2, pool="paged", page_size=4),
                        mesh=make_serving_mesh("2x4"))
            o2, m2 = e2.run(reqs)
            parity = all(np.array_equal(o1[r.rid].tokens, o2[r.rid].tokens)
                         for r in reqs)
            sharded_arena = sum(
                s.spec != jax.sharding.PartitionSpec()
                for s in jax.tree.leaves(e2._cache_sh))
            print(json.dumps({"parity": parity,
                              "skips": m2.prefill_skips,
                              "pool": m2.pool["kind"],
                              "sharded_arena": sharded_arena}))
        """)
        assert res["parity"], "sharded paged vs single-device slot mismatch"
        assert res["pool"] == "paged"
        assert res["skips"] >= 1, "prefix reuse inactive under TP"
        assert res["sharded_arena"] > 0, "page arena silently replicated"

    def test_sharded_serving_stochastic_streams_match(self):
        """Temperature/top-k sampling through the sharded tick: the
        (rid, position)-keyed streams must survive TP unchanged."""
        res = run_py("""
            import json, jax, numpy as np
            from repro import configs
            from repro.models import api
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import Engine, EngineConfig, Request

            cfg = configs.get_smoke("tinyllama-1.1b", dtype="float32",
                                    param_dtype="float32")
            params = api.init(cfg, jax.random.key(1))
            rng = np.random.RandomState(1)
            reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (5+i,)),
                            max_new_tokens=4, temperature=0.8)
                    for i in range(3)]
            e1 = Engine(cfg, params, EngineConfig(n_slots=2, top_k=8))
            o1, _ = e1.run(reqs)
            e2 = Engine(cfg, params, EngineConfig(n_slots=2, top_k=8),
                        mesh=make_serving_mesh("2x4"))
            o2, _ = e2.run(reqs)
            same = all(np.array_equal(o1[r.rid].tokens, o2[r.rid].tokens)
                       for r in reqs)
            print(json.dumps({"same": same}))
        """)
        assert res["same"], "stochastic streams diverged under TP"

    def test_sharded_serving_family_parity(self):
        """SSM states (d_inner over 'model') and encdec cross-KV through
        the sharded pool: the exotic cache layouts.  The encdec case is
        the regression lock for the partitioned sin/cos-concat
        miscompile _sinusoid works around (host-side constant)."""
        res = run_py("""
            import json, jax, numpy as np
            from repro import configs
            from repro.models import api
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import Engine, EngineConfig, Request

            out = {}
            for arch in ("falcon-mamba-7b", "whisper-large-v3"):
                cfg = configs.get_smoke(arch, dtype="float32",
                                        param_dtype="float32")
                params = api.init(cfg, jax.random.key(2))
                rng = np.random.RandomState(2)
                frames = ((lambda: rng.randn(cfg.enc_seq, cfg.d_model)
                           .astype(np.float32) * 0.1)
                          if cfg.family == "encdec" else (lambda: None))
                reqs = [Request(rid=i,
                                prompt=rng.randint(0, cfg.vocab, (4 + i,)),
                                max_new_tokens=4, frames=frames())
                        for i in range(3)]
                e1 = Engine(cfg, params, EngineConfig(n_slots=2))
                o1, _ = e1.run(reqs)
                e2 = Engine(cfg, params, EngineConfig(n_slots=2),
                            mesh=make_serving_mesh("2x4"))
                o2, _ = e2.run(reqs)
                out[arch] = all(
                    np.array_equal(o1[r.rid].tokens, o2[r.rid].tokens)
                    for r in reqs)
            print(json.dumps(out))
        """)
        for arch, ok in res.items():
            assert ok, f"sharded serving parity broke for {arch}"
