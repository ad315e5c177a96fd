"""How ``DistributedPoissonSolver.solve`` places its input on the pencils.

An input committed to the mesh's devices whose blocks differ from the
pencil's (a replicated field on a 2x2 mesh) is padded and placed by one
jitted program, each device from its own replica: ``stats["placements"]
["device"]``.  ``jax.device_put`` of such an input has no source buffer
per pencil block and copies the whole field through the host (JAX's
``shard_sharded_device_array_slow_path``); that path is never taken, and
the output equals the ``device_put`` route's bit for bit.  Every other
input keeps ``jax.device_put`` (``"put"``): one that already has the
pencil's blocks, one on other devices, one from the host.

Runs in a subprocess with 8 host devices, as ``test_distributed.py``
does, so the main test session keeps seeing a single device.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax._src.array as jarray
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.bc import BCType, DataLayout
from repro.distributed.pencil import DistributedPoissonSolver

slow = [0]
_slow_path = jarray.shard_sharded_device_array_slow_path


def counted(*a, **k):
    slow[0] += 1
    return _slow_path(*a, **k)


jarray.shard_sharded_device_array_slow_path = counted
U = (BCType.UNB, BCType.UNB)
devs = jax.devices()
mesh22 = jax.make_mesh((2, 2), ("data", "model"), devices=devs[:4])
mesh11 = jax.make_mesh((1, 1), ("data", "model"), devices=devs[:1])
mesh24 = jax.make_mesh((2, 4), ("data", "model"), devices=devs)


def solver(n, mesh, doubling="deferred"):
    return DistributedPoissonSolver((n,) * 3, 1.0, (U, U, U),
                                    layout=DataLayout.NODE, mesh=mesh,
                                    doubling=doubling)


def field(ds, batch=()):
    rng = np.random.default_rng(len(batch))
    return rng.standard_normal(batch + ds.input_shape).astype(np.float32)


def counts(ds):
    return dict(ds.stats.get("placements", {}))


def device_case(n, batch, doubling):
    ds = solver(n, mesh22, doubling)
    fh = field(ds, batch)
    f = jax.device_put(fh, NamedSharding(mesh22, P()))
    out = {"input": list(f.shape),
           "padded": list(f.shape[:-3] + ds.padded_input_shape())}
    before = slow[0]
    got = [np.asarray(ds.solve(f)) for _ in range(2)]
    out["slow_calls"] = slow[0] - before
    out["device_counts"] = counts(ds)
    out["input_alive"] = not f.is_deleted()
    out["n_places"] = len(getattr(ds, "_places", ()))
    want = np.asarray(ds.solve(fh))            # the device_put route
    out["counts_after_put"] = counts(ds)
    out["bit_equal"] = all(np.array_equal(g, want) for g in got)
    out["finite"] = bool(np.isfinite(want).all())
    return out


def put_cases():
    out = {}
    # 1x1: the input already has the pencil's blocks, no pad
    ds = solver(16, mesh11)
    f = jax.device_put(field(ds), NamedSharding(mesh11, P()))
    np.asarray(ds.solve(f))
    out["one_chip"] = {"counts": counts(ds), "n_places": len(ds._places)}
    # 2x2, from the host
    ds = solver(16, mesh22)
    fh = field(ds)
    want = np.asarray(ds.solve(fh))
    out["numpy"] = {"counts": counts(ds), "n_places": len(ds._places)}
    # 2x2, committed to one device that is not the mesh
    f1 = jax.device_put(fh, devs[0])
    got = np.asarray(ds.solve(f1))
    out["single_device"] = {"counts": counts(ds),
                            "n_places": len(ds._places),
                            "bit_equal": bool(np.array_equal(got, want)),
                            "committed": f1.committed}
    # rebuild onto another mesh shape: a fresh placement program
    rep22 = NamedSharding(mesh22, P())
    np.asarray(ds.solve(jax.device_put(fh, rep22)))
    old = dict(ds._places)
    new = ds.rebuild(mesh24)
    rep24 = NamedSharding(mesh24, P())
    got = np.asarray(new.solve(jax.device_put(fh, rep24)))
    (key,) = new._places
    # a ladder rung re-enters _configure, which drops the cache
    ds._configure(dict(ds._cfg))
    out["rebuild"] = {
        "old_counts": counts(ds), "new_counts": counts(new),
        "n_old": len(old), "n_new": len(new._places),
        "shared": any(fn is gn for fn in old.values()
                      for gn in new._places.values()),
        "new_key_devices": len(key[1].device_set),
        "close": bool(np.allclose(got, want, rtol=1e-5, atol=1e-6)),
        "after_configure": len(ds._places)}
    return out


which = sys.argv[1]
if which == "put":
    res = put_cases()
else:
    cfg = json.loads(which)
    res = device_case(cfg["n"], tuple(cfg["batch"]), cfg["doubling"])
print("RESULT " + json.dumps(res))
"""


def _run(arg):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, arg], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    (line,) = [ln for ln in out.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


DEVICE_CASES = [
    # node-centred 64^3: input 65^3, padded to 66x66x65 on 2x2
    dict(n=64, batch=[], doubling="deferred", padded=[66, 66, 65]),
    # in-block multi-RHS batch (local_batch)
    dict(n=16, batch=[2], doubling="deferred", padded=[2, 18, 18, 17]),
    # dense up-front Hockney doubling in the pad
    dict(n=16, batch=[], doubling="upfront", padded=[32, 32, 32]),
]


@pytest.mark.parametrize(
    "case", DEVICE_CASES,
    ids=lambda c: f"n{c['n']}-b{len(c['batch'])}-{c['doubling']}")
def test_replicated_input_is_placed_on_the_device(case):
    r = _run(json.dumps(case))
    assert r["padded"] == case["padded"], r
    assert r["input"] != r["padded"], r
    # no host round trip in any solve, and each solve counted
    assert r["slow_calls"] == 0, r
    assert r["device_counts"] == {"device": 2, "put": 0}, r
    # the caller's array is not donated; one cached program
    assert r["input_alive"] and r["n_places"] == 1, r
    # the same numbers as the device_put route, bit for bit
    assert r["counts_after_put"] == {"device": 2, "put": 1}, r
    assert r["bit_equal"] and r["finite"], r


@pytest.fixture(scope="module")
def put_results():
    return _run("put")


def test_one_chip_input_keeps_device_put(put_results):
    r = put_results["one_chip"]
    assert r == {"counts": {"device": 0, "put": 1}, "n_places": 0}, r


def test_host_input_keeps_device_put(put_results):
    r = put_results["numpy"]
    assert r == {"counts": {"device": 0, "put": 1}, "n_places": 0}, r


def test_single_device_input_keeps_device_put(put_results):
    r = put_results["single_device"]
    assert r["committed"], r
    assert r["counts"] == {"device": 0, "put": 2}, r
    assert r["n_places"] == 0 and r["bit_equal"], r


def test_rebuild_makes_a_new_placement(put_results):
    r = put_results["rebuild"]
    assert r["old_counts"] == {"device": 1, "put": 2}, r
    assert r["new_counts"] == {"device": 1, "put": 0}, r
    assert r["n_old"] == 1 and r["n_new"] == 1 and not r["shared"], r
    assert r["new_key_devices"] == 8, r
    assert r["close"], r
    assert r["after_configure"] == 0, r
