"""Unit tests for the Cassandra core numerics (bitops, codecs, format)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitops, coding, mx, pruning
from repro.core import format as fmt

jax.config.update("jax_platform_name", "cpu")


def rand_bf16(key, shape, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)


class TestBitops:
    def test_split_join_roundtrip(self):
        x = rand_bf16(jax.random.PRNGKey(0), (256,))
        s, e, m = bitops.split_fields(x)
        y = bitops.join_fields(s, e, m)
        np.testing.assert_array_equal(np.asarray(bitops.bf16_to_bits(x)),
                                      np.asarray(bitops.bf16_to_bits(y)))

    def test_truncate_merge_bitexact(self):
        x = rand_bf16(jax.random.PRNGKey(1), (512,))
        for keep in (0, 3, 5, 7):
            t, lo = bitops.truncate_mantissa(x, keep)
            y = bitops.merge_mantissa(t, lo, keep)
            np.testing.assert_array_equal(np.asarray(bitops.bf16_to_bits(x)),
                                          np.asarray(bitops.bf16_to_bits(y)))

    def test_truncation_is_subset(self):
        """Draft bits must be a strict subset of the original bits."""
        x = rand_bf16(jax.random.PRNGKey(2), (512,))
        t, _ = bitops.truncate_mantissa(x, 3)
        xb = np.asarray(bitops.bf16_to_bits(x)).astype(np.uint16)
        tb = np.asarray(bitops.bf16_to_bits(t)).astype(np.uint16)
        assert np.all((xb & tb) == tb)

    def test_pack_unpack_bits(self):
        b = jax.random.bernoulli(jax.random.PRNGKey(3), shape=(7, 128))
        w = bitops.pack_bits(b)
        assert w.shape == (7, 4)
        np.testing.assert_array_equal(np.asarray(bitops.unpack_bits(w, 128)),
                                      np.asarray(b))

    def test_pack_unpack_codes(self):
        for width in (3, 4, 5, 7, 12):
            codes = jax.random.randint(jax.random.PRNGKey(width), (5, 96), 0,
                                       2 ** width, dtype=jnp.int32)
            w = bitops.pack_codes(codes, width)
            out = bitops.unpack_codes(w, width, 96)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(codes).astype(np.uint32))

    def test_nibbles(self):
        v = jax.random.randint(jax.random.PRNGKey(9), (4, 10), 0, 16,
                               dtype=jnp.int32).astype(jnp.uint8)
        np.testing.assert_array_equal(
            np.asarray(bitops.unpack_nibbles(bitops.pack_nibbles(v))),
            np.asarray(v))


class TestUnaryCoding:
    def test_unary_roundtrip(self):
        key = jax.random.PRNGKey(4)
        # geometric-ish ranks like real exponent data
        ranks = jnp.minimum(
            jax.random.geometric(key, 0.35, (17, 64)) - 1, 31
        ).astype(jnp.uint8)
        n_bits = coding.region_words(64, 3) * 32
        bits, ok = coding.unary_encode_block(ranks, n_bits)
        decoded = coding.unary_decode_block(bits, 64)
        ok_np = np.asarray(ok)
        assert ok_np.any(), "sanity: some blocks must fit"
        np.testing.assert_array_equal(np.asarray(decoded)[ok_np],
                                      np.asarray(ranks)[ok_np])

    def test_unary_overflow_flagged(self):
        ranks = jnp.full((1, 64), 31, dtype=jnp.uint8)  # 32 bits/code
        bits, ok = coding.unary_encode_block(ranks, coding.region_words(64, 3) * 32)
        assert not bool(ok[0])

    def test_delta_roundtrip_exact(self):
        exps = jnp.array([[120, 119, 118, 121, 0, 121, 115, 110]],
                         dtype=jnp.uint8)
        emax = jnp.max(exps, axis=-1)
        code, corr = coding.delta_encode_block(exps, emax, 3)
        # draft view: within-range deltas exact, zero escape exact
        draft = coding.delta_decode_block(code, emax, 3)
        assert int(draft[0, 0]) == 120 and int(draft[0, 4]) == 0
        exact = coding.delta_decode_block(code, emax, 3, corr=corr)
        np.testing.assert_array_equal(np.asarray(exact), np.asarray(exps))

    def test_encode_decode_exponents_realistic(self):
        key = jax.random.PRNGKey(5)
        x = rand_bf16(key, (8, 320))
        _, exps, _ = bitops.split_fields(x)
        _, rank_of_exp = coding.build_codebook(exps)
        exp_of_rank = coding.trim_codebook(coding.build_codebook(exps)[0])
        region = coding.encode_exponents(exps, rank_of_exp, 3)
        exact = coding.decode_exponents(region, exp_of_rank, 320, 3, exact=True)
        np.testing.assert_array_equal(np.asarray(exact), np.asarray(exps))

    def test_avg_bits_below_four(self):
        """Fig. 6(b): real-ish exponents code under ~4 bits on average."""
        x = rand_bf16(jax.random.PRNGKey(6), (4096,))
        _, exps, _ = bitops.split_fields(x)
        _, rank_of_exp = coding.build_codebook(exps)
        assert float(coding.avg_code_bits(exps, rank_of_exp)) < 4.0


class TestMX:
    def test_mx_exact_within_gap8(self):
        # values within 2^4 of each other -> gap <= 4 -> bit-exact
        key = jax.random.PRNGKey(7)
        base = jax.random.uniform(key, (4, 64), minval=1.0, maxval=15.0)
        x = base.astype(jnp.bfloat16)
        enc = mx.mx_encode(x, group=32)
        dec = mx.mx_decode(enc, group=32)
        np.testing.assert_array_equal(np.asarray(bitops.bf16_to_bits(x)),
                                      np.asarray(bitops.bf16_to_bits(dec)))

    def test_mx_draft_truncation_close(self):
        x = rand_bf16(jax.random.PRNGKey(8), (4, 64))
        enc = mx.mx_encode(x, group=32)
        draft = mx.mx_decode(enc, group=32, keep_bits=4)
        err = np.abs(np.asarray(draft, np.float32) - np.asarray(x, np.float32))
        # 4 kept container bits: error below the group max * 2^-3
        gmax = np.abs(np.asarray(x, np.float32)).reshape(4, 2, 32).max(-1)
        assert np.all(err.reshape(4, 2, 32) <= gmax[..., None] * 0.25 + 1e-6)

    def test_mx_zero(self):
        x = jnp.zeros((1, 32), jnp.bfloat16)
        dec = mx.mx_decode(mx.mx_encode(x, group=32), group=32)
        assert np.all(np.asarray(dec, np.float32) == 0)


class TestPruning:
    def test_select_exact_count_and_order(self):
        key = jax.random.PRNGKey(10)
        v = rand_bf16(key, (3, 1024))
        s = jnp.abs(v.astype(jnp.float32))
        sel = pruning.select_topk_blocked(v, s, keep=320, block=512)
        assert sel["kept"].shape == (3, 2, 320)
        assert sel["pruned"].shape == (3, 2, 192)
        mask = np.asarray(bitops.unpack_bits(sel["bitmap"], 512))
        assert np.all(mask.sum(-1) == 320)

    def test_desparsify_roundtrip(self):
        key = jax.random.PRNGKey(11)
        v = rand_bf16(key, (2, 512))
        s = jnp.abs(v.astype(jnp.float32))
        sel = pruning.select_topk_blocked(v, s, keep=320, block=512)
        dense = pruning.desparsify(sel["bitmap"], sel["kept"], 512,
                                   pruned=sel["pruned"])
        np.testing.assert_array_equal(np.asarray(dense, np.float32),
                                      np.asarray(v, np.float32))

    def test_draft_zeros_at_pruned(self):
        key = jax.random.PRNGKey(12)
        v = rand_bf16(key, (1, 512))
        s = jnp.abs(v.astype(jnp.float32))
        sel = pruning.select_topk_blocked(v, s, keep=320, block=512)
        dense = pruning.desparsify(sel["bitmap"], sel["kept"], 512)
        mask = np.asarray(bitops.unpack_bits(sel["bitmap"], 512)).reshape(1, 512)
        d = np.asarray(dense, np.float32)
        assert np.all(d[~mask] == 0)
        np.testing.assert_array_equal(d[mask],
                                      np.asarray(v, np.float32)[mask])

    def test_ties_kept_exactly(self):
        v = jnp.ones((1, 512), jnp.bfloat16)  # all tied
        s = jnp.ones((1, 512))
        sel = pruning.select_topk_blocked(v, s, keep=320, block=512)
        mask = np.asarray(bitops.unpack_bits(sel["bitmap"], 512))
        assert mask.sum() == 320

    def test_keep_count(self):
        assert pruning.keep_count(512, 0.4, 32) == 320
        assert pruning.keep_count(128, 0.4, 16) == 80
        assert pruning.keep_count(512, 0.0, 32) == 512


class TestCassandraFormat:
    @pytest.mark.parametrize("shape", [(512, 64), (1024, 96)])
    def test_c1_target_bitexact(self, shape):
        """The headline lossless property: target reconstruction == original."""
        key = jax.random.PRNGKey(13)
        w = rand_bf16(key, shape)
        act = jnp.abs(jax.random.normal(jax.random.PRNGKey(14), (shape[0],)))
        cfg = fmt.CassandraConfig(variant=1)
        spec, verif = fmt.format_weight(w, act, cfg)
        back = fmt.target_weight(spec, verif, cfg, shape)
        np.testing.assert_array_equal(
            np.asarray(bitops.bf16_to_bits(w)),
            np.asarray(bitops.bf16_to_bits(back)))

    def test_c1_draft_is_subset(self):
        """Draft values: kept positions = truncated original, pruned = 0."""
        key = jax.random.PRNGKey(15)
        shape = (512, 32)
        w = rand_bf16(key, shape)
        act = jnp.ones((shape[0],))
        cfg = fmt.CassandraConfig(variant=1)
        spec, _ = fmt.format_weight(w, act, cfg)
        draft = np.asarray(fmt.draft_weight(spec, cfg, shape), np.float32)
        orig = np.asarray(w, np.float32)
        trunc = np.asarray(bitops.truncate_mantissa(w, 3)[0], np.float32)
        nz = draft != 0
        np.testing.assert_array_equal(draft[nz], trunc[nz])
        # kept fraction ~= 1 - prune ratio
        assert abs(nz.mean() - 320 / 512) < 1e-6
        # kept positions hold the high-score values
        assert np.abs(orig[nz]).mean() > np.abs(orig[~nz]).mean()

    def test_c2_target_close_draft_coarse(self):
        key = jax.random.PRNGKey(16)
        shape = (512, 32)
        w = rand_bf16(key, shape)
        cfg = fmt.CassandraConfig(variant=2)
        spec, verif = fmt.format_weight(w, None, cfg)
        back = np.asarray(fmt.target_weight(spec, verif, cfg, shape), np.float32)
        orig = np.asarray(w, np.float32)
        # MX-container reconstruction: tiny relative error on kept values
        err = np.abs(back - orig)
        assert err.max() <= np.abs(orig).max() * 2 ** -7
        draft = np.asarray(fmt.draft_weight(spec, cfg, shape), np.float32)
        nz = draft != 0
        assert abs(nz.mean() - 320 / 512) < 1e-6

    def test_kv_roundtrip_c1(self):
        key = jax.random.PRNGKey(17)
        kv = rand_bf16(key, (2, 5, 4, 128))  # (B, S, H, D)
        cfg = fmt.CassandraConfig(variant=1)
        spec, verif = fmt.format_kv(kv, cfg)
        back = fmt.target_kv(spec, verif, cfg, 128)
        np.testing.assert_array_equal(
            np.asarray(bitops.bf16_to_bits(kv)),
            np.asarray(bitops.bf16_to_bits(back.reshape(kv.shape))))
        draft = np.asarray(fmt.draft_kv(spec, cfg, 128), np.float32)
        assert abs((draft != 0).mean() - 80 / 128) < 1e-6

    def test_compression_ratio(self):
        """Draft < ~40% of bf16; spec+verif below the bf16 baseline (Fig 14)."""
        key = jax.random.PRNGKey(18)
        shape = (2048, 256)
        w = rand_bf16(key, shape)
        cfg = fmt.CassandraConfig(variant=1)
        spec, verif = fmt.format_weight(w, jnp.ones((shape[0],)), cfg)
        summary = fmt.compression_summary(spec, verif, w.size * 2)
        assert summary["draft_ratio"] < 0.42, summary
        assert summary["total_ratio"] < 1.0, summary
        cfg2 = fmt.CassandraConfig(variant=2)
        spec2, verif2 = fmt.format_weight(w, None, cfg2)
        summary2 = fmt.compression_summary(spec2, verif2, w.size * 2)
        assert summary2["draft_ratio"] < summary["draft_ratio"], (summary,
                                                                  summary2)


class TestStackedFormatting:
    @pytest.mark.parametrize("variant,calibrated", [(1, False), (1, True),
                                                    (2, False)])
    def test_per_layer_format_matches_vmap(self, variant, calibrated):
        """Formatting a (R, in, out) stack one layer at a time is bit-for-bit
        the whole-stack vmap it replaced."""
        from repro.core import packing
        w = rand_bf16(jax.random.PRNGKey(19), (3, 256, 64))
        an = (jax.random.uniform(jax.random.PRNGKey(20), (3, 256)) + 0.5
              if calibrated else None)
        cfg = fmt.CassandraConfig(variant=variant)
        got = packing._pack_weight(w, an, cfg, trim=False)
        if an is None:
            ref = jax.vmap(lambda wl: packing._format_layer(wl, None, cfg))(w)
        else:
            ref = jax.vmap(lambda wl, a: packing._format_layer(wl, a, cfg))(
                w, an)
        ref = {"spec": ref[0], "verif": ref[1]}
        assert jax.tree.structure(got) == jax.tree.structure(ref)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resolved_views_equal_on_the_fly_decode(self):
        """Views decoded once at load are bit-for-bit what ``dense``
        decodes per pass from the packed streams."""
        from repro.core import packing
        from repro.configs import get_config
        from repro.models.layers import Runtime, resolve_weight
        w = rand_bf16(jax.random.PRNGKey(21), (2, 256, 64))
        cfg = fmt.CassandraConfig(variant=1)
        packed = packing._pack_weight(w, None, cfg, trim=True)
        views = packing.resolve_views({"w": packed}, cfg)["w"]
        for view in ("draft", "target"):
            rt = Runtime(cfg=get_config("qwen3-1.7b", smoke=True), cass=cfg,
                         view=view)
            for r in range(w.shape[0]):
                layer = jax.tree.map(lambda a: a[r], packed)
                np.testing.assert_array_equal(
                    np.asarray(bitops.bf16_to_bits(resolve_weight(rt, layer))),
                    np.asarray(bitops.bf16_to_bits(
                        resolve_weight(rt, jax.tree.map(lambda a: a[r],
                                                        views)))))
        np.testing.assert_array_equal(
            np.asarray(bitops.bf16_to_bits(views["target"])),
            np.asarray(bitops.bf16_to_bits(w)))
