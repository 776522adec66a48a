"""Per-arch smoke tests + prefill/decode vs full-forward consistency."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api, blocks, transformer
from repro.serving.cache import make_paged_cache, remap_kv_leaves

ARCHS = list(configs.ARCH_IDS)


def _batch(cfg, b=2, s=16, seed=0):
    r = np.random.RandomState(seed)
    batch = {
        "tokens": jnp.asarray(r.randint(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(r.randint(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.pos == "mrope":
        batch["pos_ids"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (3, b, s))
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            r.randn(b, cfg.enc_seq, cfg.d_model) * 0.1, cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_and_loss(self, arch):
        cfg = configs.get_smoke(arch)
        params = api.init(cfg, jax.random.key(0))
        batch = _batch(cfg)
        logits = api.forward(cfg, params, batch)
        assert logits.shape == (2, 16, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        loss = api.loss_fn(cfg, params, batch)
        assert bool(jnp.isfinite(loss)) and float(loss) > 0

    def test_one_train_step_no_nans(self, arch):
        from repro.launch.steps import TrainHParams, make_train_step
        from repro.optim import adamw_init

        cfg = configs.get_smoke(arch)
        params = api.init(cfg, jax.random.key(1))
        opt = adamw_init(params)
        step = make_train_step(cfg, TrainHParams(peak_lr=1e-3, warmup=0,
                                                 total=10))
        p2, o2, metrics = jax.jit(step)(params, opt, _batch(cfg))
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        # params actually moved
        moved = any(
            bool(jnp.any(a != b))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
        assert moved


@pytest.mark.parametrize("arch", ARCHS)
class TestDecodeConsistency:
    """Teacher-forced decode must reproduce the full forward's logits.

    This validates the KV cache, the SSM state recurrence, cur_index
    masking, rope-at-position and the cache update path in one shot.
    """

    def test_prefill_then_decode_matches_forward(self, arch):
        # MoE: capacity grouping differs between full-sequence and
        # incremental paths, so dropped-token divergence is legitimate;
        # raise the capacity factor so nothing drops and the MECHANISM
        # (router, dispatch, caches) is what's tested.
        over = {"capacity_factor": 8.0} if configs.get_smoke(arch).n_experts \
            else {}
        cfg = configs.get_smoke(arch, **over)
        tol = 0.06  # bf16 noise through the stack
        params = api.init(cfg, jax.random.key(2))
        b, s = 2, 12
        batch = _batch(cfg, b=b, s=s, seed=3)
        full = api.forward(cfg, params, batch).astype(jnp.float32)

        split = s // 2
        pre_batch = {"tokens": batch["tokens"][:, :split]}
        if "pos_ids" in batch:
            pre_batch["pos_ids"] = batch["pos_ids"][:, :, :split]
        if "frames" in batch:
            pre_batch["frames"] = batch["frames"]
        logits_p, states, idx = api.prefill(cfg, params, pre_batch)
        np.testing.assert_allclose(
            np.asarray(logits_p[:, -1], np.float32),
            np.asarray(full[:, split - 1], np.float32),
            atol=tol, rtol=tol)

        # grow cache to max_seq and continue token by token
        from repro.serving.cache import SlotCachePool

        cache = SlotCachePool.grow(cfg, states, b, cfg.max_seq,
                                   jnp.dtype(cfg.dtype))
        for t in range(split, s):
            step_batch = {"token": batch["tokens"][:, t:t + 1]}
            if "pos_ids" in batch:
                step_batch["pos_ids"] = batch["pos_ids"][:, :, t:t + 1]
            lg, cache = api.decode_step(cfg, params, cache, jnp.int32(t),
                                        step_batch)
            np.testing.assert_allclose(
                np.asarray(lg[:, 0], np.float32),
                np.asarray(full[:, t], np.float32),
                atol=tol, rtol=tol)


def _slab_decode_step(cfg, params, states, cur_index, token,
                      page_table=None, page_size=0):
    """Decode with each layer's state slab sliced out of the stack,
    updated, and stacked anew: the placement the carried stack replaced.
    Same block code, so any difference is the placement's."""
    rope_cs = transformer._rope_info(cfg, token.shape[0], 1, None,
                                     cur_index=cur_index)
    x = transformer.embed_tokens(cfg, params, token)
    outs = []
    for gi in range(cfg.n_groups):
        gparams = jax.tree.map(lambda a: a[gi], params["layers"])
        slab = jax.tree.map(lambda a: a[gi:gi + 1], states)
        new = {}
        for i, kind in enumerate(cfg.block_kinds()):
            x, ns = blocks.block_apply(
                cfg, kind, gparams[f"pos{i}"], x, mode="decode",
                rope_cs=rope_cs, state=slab[f"pos{i}"], layer=0,
                cur_index=cur_index, page_table=page_table,
                page_size=page_size)
            new[f"pos{i}"] = jax.tree.map(lambda a: a[0], ns)
        outs.append(new)
    return (transformer.unembed(cfg, params, x),
            jax.tree.map(lambda *ls: jnp.stack(ls), *outs))


class TestInPlaceDecodeStack:
    """Decode carries the stacked states through the layer scan, and each
    layer reads and writes its own index of them in place.  Against the
    per-layer slab placement: the same logits, tokens and states bit for
    bit over several ticks, the KV stacks changed only at the rows the
    ticks wrote, and the unrolled stack (``scan_layers=False``) equal to
    the scanned one."""

    @pytest.mark.parametrize("arch,over,pool,kv_dtype", [
        ("tinyllama-1.1b", {}, "paged", None),         # dense GQA arena
        ("tinyllama-1.1b", {}, "paged", jnp.int8),     # int8 arena
        ("tinyllama-1.1b", {}, "slot", None),          # dense slot rows
        ("jamba-1.5-large-398b", {"capacity_factor": 8.0}, "paged",
         None),                                        # hybrid SSM + MoE
        ("granite-moe-1b-a400m", {"capacity_factor": 8.0}, "slot", None),
    ], ids=["dense-paged", "int8-paged", "dense-slot", "hybrid-moe-paged",
            "moe-slot"])
    def test_decode_ticks_match_slab_placement(self, arch, over, pool,
                                               kv_dtype):
        cfg = configs.get_smoke(arch, dtype="float32",
                                param_dtype="float32", **over)
        params = api.init(cfg, jax.random.key(4))
        rng = np.random.RandomState(4)
        b, s_max, ps, n_ticks = 3, 16, 4, 3
        if pool == "paged":
            n_pages = b * (s_max // ps) + 1  # page 0 is the trash page
            cache = make_paged_cache(cfg, b, n_pages, ps, jnp.float32,
                                     kv_dtype=kv_dtype)
            table = rng.permutation(np.arange(1, n_pages)).reshape(b, -1)
            kw = {"page_table": jnp.asarray(table, jnp.int32)}
        else:
            cache = remap_kv_leaves(api.make_cache(cfg, b, s_max,
                                                   jnp.float32), kv_dtype)
            ps, kw = 0, {}

        def fill(a):  # distinct stale contents everywhere
            if jnp.issubdtype(a.dtype, jnp.integer):
                return jnp.asarray(rng.randint(-127, 128, a.shape), a.dtype)
            return jnp.asarray(rng.randn(*a.shape) * 0.5, a.dtype)

        cache = jax.tree.map(fill, cache)
        cur0 = np.array([2, 7, 13], np.int32)
        tok0 = jnp.asarray(rng.randint(0, cfg.vocab, (b, 1)), jnp.int32)
        steps = {
            "slab": _slab_decode_step,
            "scan": transformer.decode_step,
            "unrolled": transformer.decode_step,
        }
        runs = {}
        for name, step in steps.items():
            c = (dataclasses.replace(cfg, scan_layers=False)
                 if name == "unrolled" else cfg)
            fn = jax.jit(functools.partial(step, c, page_size=ps))
            states, tok, logits = cache, tok0, []
            for t in range(n_ticks):
                lg, states = fn(params, states, jnp.asarray(cur0 + t), tok,
                                **kw)
                logits.append(np.asarray(lg))
                tok = jnp.argmax(lg[:, -1], axis=-1)[:, None].astype(
                    jnp.int32)
            runs[name] = (logits, states)

        ref_logits, ref_states = runs["slab"]
        for name in ("scan", "unrolled"):
            logits, states = runs[name]
            for t in range(n_ticks):
                np.testing.assert_array_equal(logits[t], ref_logits[t],
                                              err_msg=f"{name} tick {t}")
            for a, r in zip(jax.tree.leaves(states),
                            jax.tree.leaves(ref_states)):
                assert a.dtype == r.dtype and a.shape == r.shape
                np.testing.assert_array_equal(np.asarray(a), np.asarray(r),
                                              err_msg=name)

        # the in-place writes touch only (layer, page or slot, row) of the
        # positions the ticks wrote; every other KV row is bit-identical
        leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
        for (path, old), new in zip(leaves,
                                    jax.tree.leaves(runs["scan"][1])):
            if path[-1].key not in ("k", "v"):
                continue
            old, new = np.asarray(old), np.asarray(new)
            wrote = np.zeros(old.shape[:3], bool)
            for t in range(n_ticks):
                for i, c in enumerate(cur0 + t):
                    if pool == "paged":
                        wrote[:, table[i, c // ps], c % ps] = True
                    else:
                        wrote[:, i, c] = True
            np.testing.assert_array_equal(new[~wrote], old[~wrote])
            assert (new[wrote] != old[wrote]).any()


class TestParamAccounting:
    def test_full_config_param_counts(self):
        """Full configs land near their nameplate sizes (within 20%)."""
        expect = {
            "tinyllama-1.1b": 1.1e9,
            "internlm2-1.8b": 1.9e9,
            "granite-3-8b": 8.2e9,
            "falcon-mamba-7b": 7.3e9,
            "qwen3-moe-235b-a22b": 235e9,
            "qwen2-vl-72b": 72e9,
        }
        for arch, n in expect.items():
            cfg = configs.get_config(arch)
            got = api.param_count(cfg)
            assert abs(got - n) / n < 0.25, (arch, got, n)

    def test_active_params_moe(self):
        cfg = configs.get_config("qwen3-moe-235b-a22b")
        total = api.param_count(cfg)
        active = api.active_param_count(cfg)
        assert active < total * 0.15  # 22B active of 235B
        assert abs(active - 22e9) / 22e9 < 0.35

    def test_shape_applicability(self):
        ok, _ = configs.shape_applicable(
            configs.get_config("falcon-mamba-7b"), "long_500k")
        assert ok
        ok, why = configs.shape_applicable(
            configs.get_config("granite-3-8b"), "long_500k")
        assert not ok and "full-attention" in why


class TestFlashVariants:
    """The §Perf attention variants are numerically identical to the
    dense oracle: serial map, triangle block-skip, seq-sharded vmap."""

    @pytest.mark.parametrize("kwargs", [
        {}, {"block_skip": True}, {"seq_shard": True},
    ])
    def test_variant_matches_oracle(self, kwargs):
        from repro.core.policy import GS_FEEDBACK
        from repro.kernels import ref
        from repro.layers import attention as attn

        r = np.random.RandomState(11)
        b, h, kh, s, hd = 2, 4, 2, 128, 32
        q = r.randn(b, s, h, hd).astype(np.float32)
        k = r.randn(b, s, kh, hd).astype(np.float32)
        v = r.randn(b, s, kh, hd).astype(np.float32)
        got = np.asarray(attn.flash_chunked(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            policy=GS_FEEDBACK, causal=True, q_block=32, kv_block=64,
            **kwargs))
        want = np.asarray(ref.attention_exact(
            jnp.asarray(q.transpose(0, 2, 1, 3)),
            jnp.asarray(k.transpose(0, 2, 1, 3)),
            jnp.asarray(v.transpose(0, 2, 1, 3)),
            causal=True)).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, want, atol=2e-6)

    def test_cross_attention_unequal_lengths(self):
        from repro.core.policy import EXACT
        from repro.kernels import ref
        from repro.layers import attention as attn

        r = np.random.RandomState(12)
        q = r.randn(2, 96, 4, 32).astype(np.float32)
        k = r.randn(2, 60, 2, 32).astype(np.float32)
        v = r.randn(2, 60, 2, 32).astype(np.float32)
        got = np.asarray(attn.flash_chunked(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), policy=EXACT,
            causal=False, q_block=48, kv_block=30))
        want = np.asarray(ref.attention_exact(
            jnp.asarray(q.transpose(0, 2, 1, 3)),
            jnp.asarray(k.transpose(0, 2, 1, 3)),
            jnp.asarray(v.transpose(0, 2, 1, 3)),
            causal=False)).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, want, atol=2e-6)


class TestSeqParallelNumerics:
    """seq_parallel mode must be a pure re-sharding: identical logits."""

    def test_sp_equals_baseline(self):
        base = configs.get_smoke("minicpm-2b")
        sp = configs.get_smoke("minicpm-2b", seq_parallel=True,
                               attn_seq_shard=True, attn_q_block=8)
        params = api.init(base, jax.random.key(7))
        batch = _batch(base, b=2, s=16, seed=8)
        a = np.asarray(api.forward(base, params, batch), np.float32)
        b_ = np.asarray(api.forward(sp, params, batch), np.float32)
        np.testing.assert_allclose(a, b_, atol=3e-2, rtol=3e-2)
