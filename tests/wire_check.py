"""Subprocess body: wire accounting of the shard_map'd compressed
all-reduce (``repro.dist.compress.compressed_allreduce``).

Runs on however many devices XLA_FLAGS exposes — and, when the
``SSUMM_COORDINATOR``/``SSUMM_NUM_PROCESSES``/``SSUMM_PROCESS_ID`` env
vars are set, on a real process-spanning mesh (DESIGN.md §15), where the
int8/top-k payloads cross the process boundary. For every wire format it
asserts:

  * the psum'd byte counter equals ``n_dev × payload_bytes(tree, cfg)``
    — the exact accounting ``launch/train.py`` prints and asserts;
  * the summed tree matches a host-side reference built from the same
    per-device contributions (exact for ``none``; rtol 1e-5 for the
    codecs' f32 reduction order);
  * top-k conservation: each device's ``sent + residual`` equals its
    accumulated signal exactly — nothing dropped, only delayed;
  * the error-feedback residual is **device-local state**: every
    addressable shard of the returned residual equals the host reference
    for that device index (distinct per device, never mixed by the
    collective), and each process can only ever see its own shards.

Prints one JSON line per process; ``tests/test_distributed.py`` runs the
single-process variant, ``tests/multihost_check.py`` the 2-process one.
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json

from repro.launch.mesh import bootstrap_distributed

dist = bootstrap_distributed()  # env-driven; no-op single-process

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import make_mesh
from repro.dist.compress import (
    CompressConfig,
    compressed_allreduce,
    decode_int8,
    encode_int8,
    payload_bytes,
)

# leaf shapes chosen to exercise ceil(ratio·n), scalar broadcasting and
# multi-dim reshapes
SHAPES = {"w": (33, 7), "b": (13,), "s": ()}


def host_topk_ref(g, err, ratio):
    """The exact per-leaf math of the collective's top-k path, on host."""
    acc = g.astype(np.float32) + err
    flat = acc.ravel().copy()
    k = max(int(np.ceil(ratio * max(flat.size, 1))), 1)
    # match jax.lax.top_k tie-breaking: stable order on descending |x|
    idx = np.argsort(-np.abs(flat), kind="stable")[:k]
    vals = flat[idx].astype(g.dtype)
    sent = np.zeros_like(flat)
    sent[idx] = vals
    res = flat.copy()
    res[idx] -= vals.astype(np.float32)
    return sent.reshape(g.shape), res.reshape(g.shape), vals, idx


def main():
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    rng = np.random.default_rng(7)
    # every process derives the same full stack deterministically; each
    # device's contribution is its slice on dim 0 (all distinct)
    stacked = {k: rng.normal(size=(n_dev,) + shp).astype(np.float32)
               for k, shp in SHAPES.items()}
    spec = {k: NamedSharding(mesh, P(("data",)))
            for k in stacked}
    sharded = {
        k: jax.make_array_from_callback(
            v.shape, spec[k], lambda i, v=v: v[i])
        for k, v in stacked.items()
    }
    template = {k: np.zeros(shp, np.float32) for k, shp in SHAPES.items()}
    report = {"ok": True, "process_count": dist.process_count,
              "process_index": dist.process_index, "n_dev": n_dev,
              "errors": [], "wire_bytes": {}}

    def check(name, cond, detail=""):
        if not cond:
            report["ok"] = False
            report["errors"].append(f"{name}: {detail}")

    for kind in ("none", "int8", "topk"):
        cfg = CompressConfig(kind, topk_ratio=0.1)

        def body(x, e):
            g = jax.tree.map(lambda a: jnp.squeeze(a, 0), x)
            err = jax.tree.map(lambda a: jnp.squeeze(a, 0), e)
            s, ne, wb = compressed_allreduce(g, err, cfg, ("data",))
            if ne is None:
                ne = err
            return s, jax.tree.map(lambda a: a[None], ne), wb

        err0 = {k: np.zeros((n_dev,) + shp, np.float32)
                for k, shp in SHAPES.items()}
        err_sharded = {
            k: jax.make_array_from_callback(
                v.shape, spec[k], lambda i, v=v: v[i])
            for k, v in err0.items()
        }
        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(("data",)), P(("data",))),
            out_specs=(P(), P(("data",)), P()),
            check_vma=False,
        ))
        summed, new_err, wire = fn(sharded, err_sharded)
        wire = float(wire)
        expected = n_dev * payload_bytes(template, cfg)
        report["wire_bytes"][kind] = {"measured": wire, "priced": expected}
        check(f"{kind}/bytes", np.isclose(wire, expected, rtol=1e-6),
              f"measured {wire} != priced {expected}")

        if kind == "none":
            # the cross-process psum's partial-sum grouping (local reduce,
            # then gloo ring) differs from np.sum's left-to-right order —
            # ~1 ulp of the addends, so compare with an absolute floor
            ref = {k: v.sum(axis=0, dtype=np.float32)
                   for k, v in stacked.items()}
            for k in SHAPES:
                check(f"none/sum/{k}",
                      np.allclose(np.asarray(summed[k]), ref[k], rtol=1e-5,
                                  atol=1e-5),
                      "psum mismatch")
        elif kind == "int8":
            ref = {}
            for k, v in stacked.items():
                acc = np.zeros(SHAPES[k], np.float32)
                for i in range(n_dev):
                    q, s = encode_int8(v[i])
                    acc = acc + np.asarray(decode_int8(q, s))
                ref[k] = acc
            for k in SHAPES:
                check(f"int8/sum/{k}",
                      np.allclose(np.asarray(summed[k]), ref[k], rtol=1e-5,
                                  atol=1e-6),
                      "decoded sum mismatch")
        else:  # topk
            sent_sum = {k: np.zeros(SHAPES[k], np.float32) for k in SHAPES}
            res_ref = {k: np.zeros_like(err0[k]) for k in SHAPES}
            for k, v in stacked.items():
                for i in range(n_dev):
                    sent, res, _vals, _idx = host_topk_ref(
                        v[i], err0[k][i], cfg.topk_ratio)
                    sent_sum[k] += sent
                    res_ref[k][i] = res
                    # conservation: sent + residual == accumulated signal
                    check(f"topk/conserve/{k}/{i}",
                          np.array_equal(sent + res,
                                         v[i].astype(np.float32)),
                          "sent+residual != acc")
            for k in SHAPES:
                check(f"topk/sum/{k}",
                      np.allclose(np.asarray(summed[k]), sent_sum[k],
                                  rtol=1e-5, atol=1e-6),
                      f"{np.asarray(summed[k])} vs {sent_sum[k]}")
                # error feedback is per-device state: this process can
                # address only its own shards, and each must equal the
                # host reference for exactly that device's contribution
                shards = new_err[k].addressable_shards
                check(f"topk/err_local_count/{k}",
                      len(shards) == jax.local_device_count(),
                      f"{len(shards)} addressable err shards")
                for sh in shards:
                    i = sh.index[0].start or 0
                    got = np.asarray(sh.data)[0]
                    check(f"topk/err_local/{k}/{i}",
                          np.allclose(got, res_ref[k][i], rtol=1e-6,
                                      atol=1e-7),
                          "residual shard != per-device reference")

    print(json.dumps(report))
    raise SystemExit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
