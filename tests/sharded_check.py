"""Multi-device exactness checks for repro.core.sharded.

Run standalone in a subprocess (8 fake CPU devices) by test_sharded_knn.py:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python tests/sharded_check.py
Prints "OK <name>" per check; exits non-zero on failure.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    ExactKNN,
    fdsq_sharded,
    fqsd_ring,
    fqsd_sharded,
    knn_oracle,
    make_padded,
    pairwise_scores,
    shard_dataset,
)


def check(name, cond):
    if not cond:
        raise SystemExit(f"FAIL {name}")
    print(f"OK {name}", flush=True)


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(7)
    m, n, d, k = 8, 4096, 96, 13
    q = rng.standard_normal((m, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    ref_s, ref_i = knn_oracle(pairwise_scores(jnp.asarray(q), jnp.asarray(x), "l2"), k)
    ds = make_padded(x, row_mult=1024)  # divisible by 8 shards
    qp = jnp.pad(jnp.asarray(q), ((0, 0), (0, ds.vectors.shape[1] - d)))

    with jax.set_mesh(mesh):
        # FD-SQ over the whole mesh
        f = fdsq_sharded(mesh, k)
        v, nn = shard_dataset(mesh, ds.vectors, ds.norms, ("data", "model"))
        out = f(qp, v, nn)
        np.testing.assert_allclose(np.asarray(out.scores), np.asarray(ref_s), rtol=1e-5, atol=1e-4)
        check("fdsq_sharded scores", True)
        same = (np.asarray(out.indices) == np.asarray(ref_i)).mean()
        check(f"fdsq_sharded indices ({same:.2f})", same > 0.99)

        # FQ-SD: queries over data, dataset over model
        f2 = fqsd_sharded(mesh, k)
        v2, n2 = shard_dataset(mesh, ds.vectors, ds.norms, "model")
        out2 = f2(qp, v2, n2)
        np.testing.assert_allclose(np.asarray(out2.scores), np.asarray(ref_s), rtol=1e-5, atol=1e-4)
        check("fqsd_sharded scores", True)

        # Ring-streamed FQ-SD (fully partitioned dataset)
        f3 = fqsd_ring(mesh, k)
        v3, n3 = shard_dataset(mesh, ds.vectors, ds.norms, ("data", "model"))
        out3 = f3(qp, v3, n3)
        np.testing.assert_allclose(np.asarray(out3.scores), np.asarray(ref_s), rtol=1e-5, atol=1e-4)
        same3 = (np.asarray(out3.indices) == np.asarray(ref_i)).mean()
        check(f"fqsd_ring scores+indices ({same3:.2f})", same3 > 0.99)

        # engine facade with a mesh
        eng = ExactKNN(k=5, mesh=mesh).fit(x)
        res = eng.query(q[:1])
        rs, ri = knn_oracle(pairwise_scores(jnp.asarray(q[:1]), jnp.asarray(x)), 5)
        np.testing.assert_allclose(np.asarray(res.scores), np.asarray(rs), rtol=1e-5, atol=1e-4)
        check("engine mesh fdsq", True)

        # ip metric through the ring
        f4 = fqsd_ring(mesh, k, metric="ip")
        out4 = f4(qp, v3, n3)
        ref4_s, _ = knn_oracle(pairwise_scores(jnp.asarray(q), jnp.asarray(x), "ip"), k)
        np.testing.assert_allclose(np.asarray(out4.scores), np.asarray(ref4_s), rtol=1e-5, atol=1e-4)
        check("fqsd_ring ip", True)

        # query-direction ring (Perf iteration A) must equal the oracle too
        from repro.core.sharded import fqsd_ring_queries
        f5 = fqsd_ring_queries(mesh, k)
        out5 = f5(qp, v3, n3)
        np.testing.assert_allclose(np.asarray(out5.scores), np.asarray(ref_s), rtol=1e-5, atol=1e-4)
        same5 = (np.asarray(out5.indices) == np.asarray(ref_i)).mean()
        check(f"fqsd_ring_queries ({same5:.2f})", same5 > 0.99)

    print("ALL_OK", flush=True)


if __name__ == "__main__":
    main()
