"""TPU-native Hoplite collectives vs lax.psum on 8 host devices.

Multi-device tests run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the main pytest process keeps
a single device (system-spec requirement: only the dry-run sees many
devices)."""

import subprocess
import sys
import textwrap

import pytest


def run_subprocess(body: str):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives as C
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((8,), ("x",))
        x = np.random.RandomState(0).rand(8, 1536).astype(np.float32)
        want = np.broadcast_to(x.sum(0, keepdims=True), x.shape)

        def allreduce_of(fn):
            g = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
            with jax.set_mesh(mesh):
                return np.asarray(jax.jit(g)(x))
        """
    ) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        cwd=".",
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


@pytest.mark.parametrize(
    "expr",
    [
        "C.chain_allreduce(a, 'x', num_chunks=4)",
        "C.chain_allreduce(a, 'x', num_chunks=16)",
        "C.two_level_allreduce(a, 'x', num_chunks=4)",
        "C.rs_ag_allreduce(a, 'x')",
        "C.hoplite_psum(a, 'x')",
    ],
)
def test_allreduce_variants_match_psum(expr):
    run_subprocess(
        f"""
        out = allreduce_of(lambda a: {expr})
        np.testing.assert_allclose(out, want, rtol=1e-5)
        print("ok")
        """
    )


def test_chain_reduce_and_broadcast():
    run_subprocess(
        """
        f = jax.shard_map(lambda a: C.chain_reduce(a, "x", 4), mesh=mesh,
                          in_specs=P("x"), out_specs=P("x"))
        with jax.set_mesh(mesh):
            got = np.asarray(jax.jit(f)(x))
        np.testing.assert_allclose(got[7], x.sum(0), rtol=1e-5)

        y = np.zeros((8, 64), np.float32); y[7] = 2.5
        f2 = jax.shard_map(lambda a: C.chain_broadcast(a, "x", 4), mesh=mesh,
                           in_specs=P("x"), out_specs=P("x"))
        with jax.set_mesh(mesh):
            got2 = np.asarray(jax.jit(f2)(y))
        np.testing.assert_allclose(got2, 2.5)
        print("ok")
        """
    )


def test_binomial_broadcast_all_roots():
    run_subprocess(
        """
        for root in (0, 3, 7):
            z = np.zeros((8, 16), np.float32); z[root] = root + 1.0
            f = jax.shard_map(lambda a, r=root: C.binomial_broadcast(a, "x", r),
                              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
            with jax.set_mesh(mesh):
                got = np.asarray(jax.jit(f)(z))
            np.testing.assert_allclose(got, root + 1.0)
        print("ok")
        """
    )


def test_pairwise_exchange_n2():
    run_subprocess(
        """
        mesh2 = auto_mesh((2, 4), ("p", "x"))
        xx = np.random.RandomState(1).rand(2, 4, 32).astype(np.float32)
        g = jax.shard_map(lambda a: C.chain_allreduce(a, "p", 8), mesh=mesh2,
                          in_specs=P("p", "x"), out_specs=P("p", "x"))
        with jax.set_mesh(mesh2):
            out = np.asarray(jax.jit(g)(xx))
        want = np.broadcast_to(xx.sum(0, keepdims=True), xx.shape)
        np.testing.assert_allclose(out, want, rtol=1e-6)
        print("ok")
        """
    )


def test_grad_sync_tree_methods():
    run_subprocess(
        """
        tree = {"a": x, "b": x[:, :17] * 2}
        for method in ("psum", "hoplite_chain", "hoplite_2d"):
            def sync(t):
                return C.grad_sync(t, "x", method=method, mean=True)
            g = jax.shard_map(sync, mesh=mesh, in_specs=({"a": P("x"), "b": P("x")},),
                              out_specs={"a": P("x"), "b": P("x")})
            with jax.set_mesh(mesh):
                out = jax.jit(g)(tree)
            np.testing.assert_allclose(np.asarray(out["a"]), want / 8, rtol=1e-5)
        for method in ("hoplite", "chain", "chain2d", "rs_ag"):  # not grad_sync methods
            g = jax.shard_map(lambda t: C.grad_sync(t, "x", method), mesh=mesh,
                              in_specs=(P("x"),), out_specs=P("x"))
            try:
                with jax.set_mesh(mesh):
                    jax.jit(g)(x)
            except ValueError as e:
                assert method in str(e), e
            else:
                raise AssertionError(f"grad_sync accepted {method!r}")
        print("ok")
        """
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "fn", ["chain_allreduce", "chain_reduce", "chain_broadcast", "two_level_allreduce"]
)
def test_chunked_schedules_are_bitwise_their_fold(fn, dtype):
    """Leaves whose size is no multiple of C x rows x 128, so every chunk
    carries padding: each rank's result is bitwise the sum in the
    schedule's own order (a chain folds rank 0 first)."""
    run_subprocess(
        f"""
        import functools

        def fold(parts):
            acc = parts[0]
            for p in parts[1:]:
                acc = acc + p
            return acc

        g, m = C.two_level_group_sizes(8)
        for shape, nc in (((1000,), 4), ((3, 1000), 5)):
            xs = np.random.RandomState(7).randn(8, *shape).astype(jnp.{dtype})
            cases = {{
                "chain_allreduce": [(C.chain_allreduce, [fold(xs)] * 8)],
                "chain_reduce": [(C.chain_reduce, [fold(xs[: i + 1]) for i in range(8)])],
                "chain_broadcast": [
                    (C.chain_broadcast, [xs[7]] * 8),
                    (functools.partial(C.chain_broadcast, root="first"), [xs[0]] * 8),
                ],
                "two_level_allreduce": [
                    (C.two_level_allreduce,
                     [fold([fold(xs[q * g:(q + 1) * g]) for q in range(m)])] * 8),
                ],
            }}["{fn}"]
            for op, want in cases:
                f = jax.shard_map(lambda a: op(a[0], "x", nc)[None], mesh=mesh,
                                  in_specs=P("x"), out_specs=P("x"))
                with jax.set_mesh(mesh):
                    got = np.asarray(jax.jit(f)(xs))
                want = np.stack(want)
                assert got.dtype == want.dtype, (got.dtype, want.dtype)
                assert got.tobytes() == want.tobytes(), (shape, nc, np.abs(
                    got.astype(np.float32) - want.astype(np.float32)).max())
        print("ok")
        """
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_chunk_layout_is_whole_tiles(dtype):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import collectives as C

    x = jnp.asarray(np.arange(3000).reshape(3, 1000) % 101, dtype)
    unit = 8 * 4 // jnp.dtype(dtype).itemsize
    for nc in (1, 4, 5, 47):
        chunks, orig = C._to_chunks(x, nc)
        assert chunks.dtype == x.dtype and orig == x.size
        assert chunks.shape[0] == nc and chunks.shape[2] == 128, chunks.shape
        assert chunks.shape[1] % unit == 0, chunks.shape
        assert chunks.size - x.size < nc * unit * 128
        back = C._from_chunks(chunks, orig, x.shape, x.dtype)
        assert back.shape == x.shape and back.dtype == x.dtype
        assert np.asarray(back).tobytes() == np.asarray(x).tobytes()
