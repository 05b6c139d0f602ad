"""Shape-bucketing subsystem tests (ISSUE 1 tentpole).

Covers the io half (BucketedBatchSampler + PadToBucket), the jit half
(bucket-aware compile cache, cache_stats telemetry, eager-fallback
counters/marks, FLAGS-gated compile-cliff warning), and the acceptance
criterion: a DataLoader stream of >= 20 distinct sequence lengths through a
jitted train step compiles at most once per bucket, vs once per shape
without bucketing.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import io, jit


@pytest.fixture(autouse=True)
def _clean_cache_stats():
    jit.reset_cache_stats()
    prev = jit.set_shape_buckets(None)
    yield
    jit.set_shape_buckets(None)
    if prev is not None:
        jit.set_shape_buckets(prev.axes)
    jit.reset_cache_stats()


class VarLenDataset(io.Dataset):
    """(ids[L], label) samples covering every length in [lo, hi)."""

    def __init__(self, n, lo=3, hi=27, vocab=50, seed=0):
        rng = np.random.RandomState(seed)
        # guarantee full coverage of [lo, hi) then fill randomly
        lens = list(range(lo, hi)) + list(rng.randint(lo, hi, max(0, n - (hi - lo))))
        self.samples = [
            (rng.randint(1, vocab, (L,)).astype(np.int64),
             np.int64(L % 2))
            for L in lens[:max(n, hi - lo)]
        ]

    def __len__(self):
        return len(self.samples)

    def __getitem__(self, i):
        return self.samples[i]


class TinyClassifier(nn.Layer):
    def __init__(self, vocab=50, dim=8):
        super().__init__()
        self.emb = nn.Embedding(vocab, dim)
        self.fc = nn.Linear(dim, 2)

    def forward(self, ids, mask):
        h = self.emb(ids) * mask.unsqueeze(-1)
        h = h.sum(axis=1) / mask.sum(axis=1, keepdim=True).clip(min=1.0)
        return self.fc(h)


class TestBucketSpec:
    def test_normalize_and_pad_dims(self):
        spec = jit.BucketSpec.normalize([64, 16, 128])
        assert spec.axes == {1: (16, 64, 128)}
        assert spec.bucketed_dim(1, 1) == 16
        assert spec.bucketed_dim(1, 16) == 16
        assert spec.bucketed_dim(1, 17) == 64
        assert spec.bucketed_dim(1, 128) == 128
        # overflow passes through unbucketed
        assert spec.bucketed_dim(1, 129) == 129
        # unregistered axes untouched
        assert spec.bucketed_dim(0, 7) == 7

    def test_dict_spec_and_pad_widths(self):
        spec = jit.BucketSpec.normalize({0: [4], 1: [8, 16]})
        assert spec.pad_widths((4, 8)) is None
        assert spec.pad_widths((3, 9)) == [(0, 1), (0, 7)]

    def test_invalid(self):
        with pytest.raises(ValueError):
            jit.BucketSpec.normalize([8, 8])
        with pytest.raises(ValueError):
            jit.BucketSpec.normalize([0, 8])
        with pytest.raises(ValueError):
            jit.BucketSpec.normalize([])


class TestBucketedBatchSampler:
    def test_batches_stay_in_bucket_and_cover_all(self):
        ds = VarLenDataset(40)
        sampler = io.BucketedBatchSampler(ds, batch_size=4,
                                          boundaries=[8, 16, 32],
                                          shuffle=True, seed=3)
        bounds = (8, 16, 32)
        seen = []
        for batch in sampler:
            lens = [len(ds[i][0]) for i in batch]
            # all lengths in a batch pad to the SAME boundary
            import bisect

            buckets = {bisect.bisect_left(bounds, n) for n in lens}
            assert len(buckets) == 1
            seen.extend(batch)
        assert sorted(seen) == list(range(len(ds)))
        assert len(list(sampler)) == len(sampler)

    def test_drop_last_and_histogram(self):
        ds = VarLenDataset(30)
        sampler = io.BucketedBatchSampler(ds, batch_size=4,
                                          boundaries=[8, 16, 32],
                                          drop_last=True)
        for batch in sampler:
            assert len(batch) == 4
        hist = sampler.bucket_histogram()
        assert sum(hist.values()) == len(ds)

    def test_precomputed_lengths_skip_dataset_scan(self):
        class Exploding(io.Dataset):
            def __len__(self):
                return 6

            def __getitem__(self, i):
                raise AssertionError("scanned dataset despite lengths=")

        sampler = io.BucketedBatchSampler(Exploding(), batch_size=2,
                                          boundaries=[8],
                                          lengths=[3, 5, 2, 8, 1, 4])
        assert len(sampler) == 3

    def test_requires_boundaries(self):
        with pytest.raises(ValueError):
            io.BucketedBatchSampler(VarLenDataset(4), batch_size=2)


class TestPadToBucket:
    def test_pads_to_boundary_with_mask(self):
        collate = io.PadToBucket([8, 16])
        samples = [(np.arange(1, 6, dtype=np.int64), np.int64(0)),
                   (np.arange(1, 4, dtype=np.int64), np.int64(1))]
        ids, label, mask = collate(samples)
        assert ids.shape == [2, 8] and mask.shape == [2, 8]
        np.testing.assert_array_equal(mask.numpy().sum(1), [5, 3])
        np.testing.assert_array_equal(ids.numpy()[0, 5:], 0)
        np.testing.assert_array_equal(label.numpy(), [0, 1])

    def test_dict_samples_and_numpy_mode(self):
        collate = io.PadToBucket([4], as_tensor=False, mask_key="valid")
        out = collate([{"x": np.ones(2, np.float32), "y": 1.5},
                       {"x": np.ones(3, np.float32), "y": 2.5}])
        assert isinstance(out["x"], np.ndarray) and out["x"].shape == (2, 4)
        np.testing.assert_array_equal(out["valid"].sum(1), [2, 3])
        np.testing.assert_allclose(out["y"], [1.5, 2.5])

    def test_overflow_pads_to_batch_max(self):
        collate = io.PadToBucket([4])
        ids, mask = collate([np.ones(9, np.int64), np.ones(7, np.int64)])
        assert ids.shape == [2, 9]

    def test_explicit_pad_fields(self):
        # second field is fixed-size and must NOT be padded even though a
        # sample's length can coincide with it
        collate = io.PadToBucket([8], pad_fields=(0,))
        samples = [(np.ones(3, np.int64), np.ones(3, np.float32)),
                   (np.ones(3, np.int64), np.ones(3, np.float32))]
        ids, feats, mask = collate(samples)
        assert ids.shape == [2, 8]
        assert feats.shape == [2, 3]

    def test_picklable_for_process_workers(self):
        import pickle

        collate = pickle.loads(pickle.dumps(
            io.PadToBucket([8], as_tensor=False)))
        out, mask = collate([np.ones(3, np.int64)])
        assert out.shape == (1, 8)


def _train_arm(boundaries, batch_size, shape_buckets=None, drop_last=False):
    """One A/B arm: drive the full VarLen stream through a jitted train
    step; returns (stats_name, n_batches, distinct_input_widths)."""
    paddle.seed(0)
    ds = VarLenDataset(48, lo=3, hi=27)  # lengths 3..26 -> 24 distinct
    net = TinyClassifier()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())

    @jit.to_static(shape_buckets=shape_buckets)
    def train_step(ids, label, mask):
        logits = net(ids, mask)
        return F.cross_entropy(logits, label)

    sampler = (io.BucketedBatchSampler(ds, batch_size=batch_size,
                                       boundaries=boundaries,
                                       drop_last=drop_last)
               if boundaries else
               io.BatchSampler(ds, batch_size=batch_size))
    collate = io.PadToBucket(boundaries or [])
    loader = io.DataLoader(ds, batch_sampler=sampler, collate_fn=collate)
    widths = set()
    n_batches = 0
    for ids, label, mask in loader:
        widths.add(ids.shape[1])
        loss = train_step(ids, label, mask)
        loss.backward()
        opt.step()
        opt.clear_grad()
        n_batches += 1
    return train_step._stats_name, n_batches, widths


class TestCompileCacheAcceptance:
    """The ISSUE acceptance criterion, both arms."""

    def test_bucketed_stream_compiles_at_most_once_per_bucket(self):
        # drop_last: a trailing partial batch varies the BATCH axis, which
        # is its own (legitimate) compile — static-shape pipelines drop it
        boundaries = [8, 16, 32]
        name, n_batches, widths = _train_arm(boundaries, batch_size=4,
                                             shape_buckets=None,
                                             drop_last=True)
        stats = jit.cache_stats(name)
        assert widths <= set(boundaries)
        assert stats["compiles"] <= len(boundaries)
        assert stats["hits"] == n_batches - stats["compiles"]
        assert stats["eager_fallbacks"] == 0
        assert sum(stats["per_shape_misses"].values()) == stats["compiles"]

    def test_unbucketed_stream_compiles_once_per_shape(self):
        # batch_size=1, pad-to-exact-length collate: every distinct sample
        # length is its own XLA compile — the cliff this PR kills
        name, n_batches, widths = _train_arm(None, batch_size=1)
        assert len(widths) >= 20, "stream must cover >= 20 distinct lengths"
        stats = jit.cache_stats(name)
        assert stats["compiles"] == len(widths)
        assert stats["hits"] == n_batches - stats["compiles"]
        assert len(stats["per_shape_misses"]) == len(widths)

    def test_jit_side_buckets_alone_cap_compiles(self):
        # no sampler/collate cooperation: plain per-length batches, buckets
        # registered only on the jit side (shape_buckets kwarg)
        name, n_batches, widths = _train_arm(None, batch_size=1,
                                             shape_buckets=[8, 16, 32])
        assert len(widths) >= 20
        stats = jit.cache_stats(name)
        assert stats["compiles"] <= 3
        assert stats["hits"] == n_batches - stats["compiles"]
        assert stats["bucket_pads"] > 0

    def test_global_shape_buckets_apply(self):
        jit.set_shape_buckets([8, 16, 32])
        name, n_batches, widths = _train_arm(None, batch_size=1)
        assert len(widths) >= 20
        stats = jit.cache_stats(name)
        assert stats["compiles"] <= 3


class TestCacheTelemetry:
    def test_eager_fallback_counted_and_marked(self):
        from paddle_tpu.observability.trace import TRACER

        @jit.to_static
        def f(x):
            if float(x.sum()) > 0:  # data-dependent -> SOT fallback
                return x * 2
            return x * 3

        TRACER.clear()
        TRACER.enable()
        try:
            with pytest.warns(UserWarning, match="Falling back to EAGER"):
                f(paddle.to_tensor(np.ones(4, np.float32)))
            for _ in range(3):
                f(paddle.to_tensor(np.ones(4, np.float32)))
        finally:
            TRACER.disable()
        stats = jit.cache_stats(f._stats_name)
        assert stats["eager_fallbacks"] == 4
        assert stats["compiles"] == 0
        marks = [e["name"] for e in TRACER.drain()
                 if e["name"].startswith("jit::eager_fallback::")]
        assert len(marks) == 4

    def test_compile_cliff_warning_is_flag_gated(self):
        @jit.to_static
        def g(x):
            return x * 2

        old = paddle.get_flags("FLAGS_jit_compile_warn_threshold")
        paddle.set_flags({"FLAGS_jit_compile_warn_threshold": 2})
        try:
            with pytest.warns(UserWarning, match="recompile-per-shape"):
                for L in range(3, 7):
                    g(paddle.to_tensor(np.ones(L, np.float32)))
        finally:
            paddle.set_flags(old)

    def test_counting_jit_takes_jaxs_word_for_a_call_it_knew(self, monkeypatch):
        # a call jax's own cache of the function knew is a hit, counted
        # without the arguments' signature being built; a new shape, a new
        # dtype or a new static value is a compile under its signature
        import jax.numpy as jnp

        from paddle_tpu.jit.cache import CountingJit

        f = CountingJit(lambda xs, n, y: [x * n for x in xs] + [y], "cj_t",
                        static_argnums=(1,))
        built = []
        sig = CountingJit._signature
        monkeypatch.setattr(CountingJit, "_signature",
                            lambda self, a: built.append(1) or sig(self, a))
        xs = [jnp.ones((2, 3)), jnp.ones(4)]
        for _ in range(4):
            f(xs, 2, jnp.zeros(1, jnp.int32))
        assert len(built) == 1
        f(xs, 3, jnp.zeros(1, jnp.int32))                # static value
        f(xs, 2, jnp.zeros(1, jnp.float32))              # dtype
        f([jnp.ones((2, 5)), jnp.ones(4)], 2, jnp.zeros(1, jnp.int32))
        for _ in range(3):
            f(xs, 3, jnp.zeros(1, jnp.int32))
        stats = jit.cache_stats("cj_t")
        assert stats["compiles"] == 4 and stats["hits"] == 6
        assert len(built) == 4
        assert sorted(stats["per_shape_misses"].values()) == [1, 1, 1, 1]
        assert any(k.endswith("||3") for k in stats["per_shape_misses"])

    def test_reset_cache_stats(self):
        @jit.to_static
        def h(x):
            return x + 1

        h(paddle.to_tensor(np.ones(3, np.float32)))
        assert jit.cache_stats(h._stats_name)["compiles"] == 1
        jit.reset_cache_stats()
        assert jit.cache_stats() == {}


class TestFusedTrainStepBuckets:
    def test_fused_step_bucketed_compiles(self):
        paddle.seed(0)
        net = TinyClassifier()

        class WithLoss(nn.Layer):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def forward(self, ids, label, mask):
                return F.cross_entropy(self.inner(ids, mask), label)

        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        step = paddle.incubate.fused_train_step(
            WithLoss(net), opt, shape_buckets=[8, 16])
        rng = np.random.RandomState(0)
        losses = []
        for L in range(3, 15):
            ids = paddle.to_tensor(rng.randint(1, 50, (2, L)).astype("int64"))
            mask = paddle.to_tensor(np.ones((2, L), np.float32))
            label = paddle.to_tensor(rng.randint(0, 2, (2,)).astype("int64"))
            losses.append(float(step(ids, label, mask).numpy()))
        assert all(np.isfinite(losses))
        stats = jit.cache_stats(step._stats_name)
        assert stats["compiles"] <= 2
        assert stats["hits"] == 12 - stats["compiles"]
        assert stats["bucket_pads"] > 0


class TestDominantLengthRule:
    """Bucket padding must follow the dominant-length rule: only inputs
    whose bucketed axis matches the call's length (first carrier of the
    axis) are padded — fixed-size fields pass through untouched."""

    def test_fixed_size_fields_not_padded(self):
        spec = jit.BucketSpec.normalize([8, 16])
        ids = np.ones((2, 5), np.int64)       # length carrier -> pads to 8
        dense = np.ones((2, 13), np.float32)  # fixed-size -> untouched
        label = np.ones((2, 1), np.int64)     # fixed-size -> untouched
        from paddle_tpu.jit.cache import infer_call_lengths, \
            pad_array_to_bucket

        lengths = infer_call_lengths([ids, dense, label], spec)
        assert lengths == {1: 5}
        out, p = pad_array_to_bucket(ids, spec, lengths)
        assert p and out.shape == (2, 8)
        out, p = pad_array_to_bucket(dense, spec, lengths)
        assert not p and out.shape == (2, 13)
        out, p = pad_array_to_bucket(label, spec, lengths)
        assert not p and out.shape == (2, 1)

    def test_fused_step_leaves_dense_features_alone(self):
        paddle.seed(0)

        class DenseNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = nn.Embedding(50, 8)
                self.fc = nn.Linear(8 + 13, 2)

            def forward(self, ids, dense, label, mask):
                h = self.emb(ids) * mask.unsqueeze(-1)
                h = h.sum(axis=1) / mask.sum(axis=1, keepdim=True)
                logits = self.fc(paddle.concat([h, dense], axis=1))
                return F.cross_entropy(logits, label)

        m = DenseNet()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters())
        step = paddle.incubate.fused_train_step(m, opt,
                                                shape_buckets=[8, 16])
        rng = np.random.RandomState(0)
        for L in (3, 7, 12):
            ids = paddle.to_tensor(rng.randint(1, 50, (2, L)).astype("int64"))
            mask = paddle.to_tensor(np.ones((2, L), np.float32))
            dense = paddle.to_tensor(rng.randn(2, 13).astype("float32"))
            label = paddle.to_tensor(rng.randint(0, 2, (2,)).astype("int64"))
            loss = step(ids, dense, label, mask)
            assert np.isfinite(float(loss.numpy()))
        stats = jit.cache_stats(step._stats_name)
        # dense [2, 13] never bucketed: 2 shapes (bucket 8, bucket 16), and
        # the fc(8+13) would have shape-errored had dense been padded
        assert stats["compiles"] == 2

    def test_eager_fallback_with_buckets_keeps_shapes_and_skips_padding(self):
        @jit.to_static(shape_buckets=[8, 16])
        def f(x):
            if float(x.sum()) > -1e9:  # data-dependent -> SOT fallback
                return x * 2
            return x

        with pytest.warns(UserWarning, match="Falling back to EAGER"):
            out = f(paddle.to_tensor(np.ones((2, 5), np.float32)))
        assert out.shape == [2, 5]  # ORIGINAL shape, not the bucket
        pads_after_first = jit.cache_stats(f._stats_name)["bucket_pads"]
        for _ in range(3):
            out = f(paddle.to_tensor(np.ones((2, 5), np.float32)))
            assert out.shape == [2, 5]
        stats = jit.cache_stats(f._stats_name)
        # known-eager calls short-circuit on the shape-level key: no new
        # pad materialization after the first (failed-trace) call
        assert stats["bucket_pads"] == pads_after_first
        assert stats["eager_fallbacks"] == 4

    def test_bucket_args_escape_hatch_on_length_coincidence(self):
        """seq_len == n_dense_features (13) would fool the auto rule into
        padding the dense field; bucket_args pins the padded inputs."""
        paddle.seed(0)

        class DenseNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = nn.Embedding(50, 8)
                self.fc = nn.Linear(8 + 13, 2)

            def forward(self, ids, dense, label, mask):
                h = self.emb(ids) * mask.unsqueeze(-1)
                h = h.sum(axis=1) / mask.sum(axis=1, keepdim=True)
                logits = self.fc(paddle.concat([h, dense], axis=1))
                return F.cross_entropy(logits, label)

        m = DenseNet()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters())
        step = paddle.incubate.fused_train_step(
            m, opt, shape_buckets=[8, 16], bucket_args=(0, 3))  # ids, mask
        rng = np.random.RandomState(0)
        for L in (3, 13, 14):  # 13 collides with the dense width
            ids = paddle.to_tensor(rng.randint(1, 50, (2, L)).astype("int64"))
            mask = paddle.to_tensor(np.ones((2, L), np.float32))
            dense = paddle.to_tensor(rng.randn(2, 13).astype("float32"))
            label = paddle.to_tensor(rng.randint(0, 2, (2,)).astype("int64"))
            loss = step(ids, dense, label, mask)
            assert np.isfinite(float(loss.numpy()))
        assert jit.cache_stats(step._stats_name)["compiles"] == 2

    def test_to_static_bucket_args(self):
        net = TinyClassifier()

        @jit.to_static(shape_buckets=[8, 16], bucket_args=(0, "mask"))
        def fwd(ids, mask=None):
            return net(ids, mask)

        rng = np.random.RandomState(0)
        for L in (3, 7, 12):
            ids = paddle.to_tensor(rng.randint(1, 50, (2, L)).astype("int64"))
            mask = paddle.to_tensor(np.ones((2, L), np.float32))
            out = fwd(ids, mask=mask)
            assert out.shape == [2, 2]
        assert jit.cache_stats(fwd._stats_name)["compiles"] == 2


class TestCompileCachePlacement:
    """jit.cache.place_compile_cache (ISSUE 21): the persistent compile
    cache is placed from outside when JAX_COMPILATION_CACHE_DIR is set,
    else at a fixed path inside the checkout."""

    def test_env_set_leaves_the_config_alone(self, monkeypatch, tmp_path):
        import jax

        from paddle_tpu.jit.cache import place_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert place_compile_cache() == str(tmp_path)
        assert calls == []

    def test_unset_places_it_in_the_checkout(self, monkeypatch):
        import os

        import jax

        from paddle_tpu.jit.cache import place_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = place_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

