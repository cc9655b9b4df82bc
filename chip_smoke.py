#!/usr/bin/env python3
"""Chip smoke: drive the served CURP update path once on one TPU and check
its answers against the Python reference.

    python chip_smoke.py [--seed N] [--keys N] [--zipf-batches N]
                         [--mset-batches N]

Deployment: ``repro.core.ShardedCluster(n_shards=16, f=3,
witness_backend="device")`` at the paper's witness geometry (1024 sets x 4
ways per witness, §B.1): 48 witnesses stacked into one 64-lane gang, six
[4, 65536] planes (6 MiB) on the device, plus 16 master-window rings of 1024
entries.  Traffic, all made from ``--seed``:

  1. load ``--keys`` keys with 100 B values through ``update_batch``;
  2. zipfian (theta=0.99) single-key SET/INCR batches with the normal
     sync/gc rounds: the fused ``gang_fastpath_batch`` and ``gang_gc`` path;
  3. batches carrying one same-shard MSET op, which decline to the per-shard
     path (``gang_record`` and ``gang_record_groups``), plus two-shard
     ``mset`` calls;
  4. one master crash, one more batch, then every acknowledged key read back.

A ``witness_backend="python"`` cluster runs the same traffic as the
reference: per-op outcomes and every read-back value must be identical, and
both must equal a plain dict model of the acknowledged writes.

Exits non-zero, printing no result, when JAX finds no TPU: it never falls
back to the CPU or to the Pallas interpreter.  The last stdout line is one
JSON object naming the device.  Times printed on earlier lines are smoke
times, not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_SHARDS, F = 16, 3
N_SETS, N_WAYS = 1024, 4
BATCH = 1024  # the batch size tests/test_tpu_compile.py rehearses
VALUE_BYTES = 100
THETA = 0.99
MAIN_PATH_IMPLS = ("_gang_fastpath_impl", "_gang_gc_impl",
                   "_gang_record_impl", "_gang_groups_impl")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keys", type=int, default=1_000_000,
                   help="keys loaded before the update phases")
    p.add_argument("--zipf-batches", type=int, default=200)
    p.add_argument("--mset-batches", type=int, default=20)
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# Traffic (host-side, from the seed; identical for both backends)
# ---------------------------------------------------------------------------
class Workload:
    """Seeded YCSB-style traffic: ``user%07d`` keys, 100 B values, zipfian
    key choice over a random rank permutation (hot keys spread over
    shards).  ``shard_of`` is the cluster's key placement, used only to
    pick same-shard and two-shard MSET pairs."""

    def __init__(self, seed: int, n_keys: int, batch: int,
                 zipf_batches: int, mset_batches: int, shard_of,
                 n_shards: int = N_SHARDS) -> None:
        self.rng = np.random.default_rng(seed)
        self.n_keys, self.batch = n_keys, batch
        self.keys = [f"user{i:07d}" for i in range(n_keys)]
        self.load = list(zip(self.keys, self._values(n_keys)))
        cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -THETA)
        self._cdf = cdf / cdf[-1]
        self._perm = self.rng.permutation(n_keys)
        self.zipf = [self._updates(batch) for _ in range(zipf_batches)]
        # Each MSET batch: batch-1 single-key updates + one same-shard MSET
        # op, then a few two-shard ``mset`` calls.
        self.mixed = [(self._updates(batch - 1), self._msets(shard_of, True, 1)[0],
                       self._msets(shard_of, False, 4))
                      for _ in range(mset_batches)]
        self.crash_shard = int(self.rng.integers(n_shards))
        self.post_crash = self._updates(batch)

    def _values(self, n: int):
        raw = self.rng.integers(0, 256, size=(n, VALUE_BYTES // 2),
                                dtype=np.uint8).tobytes().hex()
        return [raw[i * VALUE_BYTES:(i + 1) * VALUE_BYTES] for i in range(n)]

    def _zipf_keys(self, n: int):
        idx = np.minimum(np.searchsorted(self._cdf, self.rng.random(n)),
                         self.n_keys - 1)
        return [self.keys[int(i)] for i in self._perm[idx]]

    def _updates(self, n: int):
        """(kind, key, value): half SETs of fresh 100 B values, half INCRs."""
        keys = self._zipf_keys(n)
        is_set = self.rng.random(n) < 0.5
        vals = iter(self._values(int(is_set.sum())))
        return [("set", k, next(vals)) if s else ("incr", k, None)
                for k, s in zip(keys, is_set)]

    def _msets(self, shard_of, same_shard: bool, n: int):
        """n two-key MSETs [(k1, v1), (k2, v2)] whose keys share a shard
        (or sit on two shards); keys are zipfian, distinct within a pair."""
        out = []
        while len(out) < n:
            a, b = self._zipf_keys(2)
            if a != b and (shard_of(a) == shard_of(b)) == same_shard:
                va, vb = self._values(2)
                out.append([(a, va), (b, vb)])
        return out

    def model(self) -> dict:
        """Plain dict model of every acknowledged write, in issue order."""
        model = dict(self.load)

        def apply(updates):
            for kind, k, v in updates:
                cur = model.get(k)
                model[k] = v if kind == "set" else (
                    (cur if isinstance(cur, int) else 0) + 1)
        for updates in self.zipf:
            apply(updates)
        for updates, mset, cross in self.mixed:
            apply(updates)
            for kvs in [mset] + cross:
                model.update(kvs)
        apply(self.post_crash)
        return model


# ---------------------------------------------------------------------------
# One backend end to end
# ---------------------------------------------------------------------------
def run_backend(backend: str, wl: Workload, *, n_shards: int = N_SHARDS,
                f: int = F, n_sets: int = N_SETS, n_ways: int = N_WAYS,
                log=print) -> dict:
    """Drive the workload through ``ShardedCluster`` with one witness
    backend; returns per-op outcomes, read-back values and counters."""
    from repro.core import ShardedCluster, WitnessGeometry

    cl = ShardedCluster(n_shards=n_shards, f=f, witness_backend=backend,
                        geometry=WitnessGeometry(n_sets, n_ways))
    s = cl.new_client()
    fused = cl._fused
    outcomes, written = [], set()
    single_declines = 0

    def ops_of(updates):
        return [s.op_set(k, v) if kind == "set" else s.op_incr(k)
                for kind, k, v in updates]

    def single_key_batch(updates):
        nonlocal single_declines
        before = fused.stats["declined"] if fused else 0
        outcomes.extend(_row(o) for o in cl.update_batch(s, ops_of(updates)))
        single_declines += (fused.stats["declined"] - before) if fused else 0
        written.update(k for _kind, k, _v in updates)

    def phase(name, fn, items):
        t = time.perf_counter()
        for item in items:
            fn(item)
        log(f"[{backend}] {name}: {time.perf_counter() - t:.1f} s smoke time")

    def mixed_batch(item):
        # The same-shard MSET op makes the batch decline the fused path:
        # single-key shards then run gang_record, the MSET's shard
        # gang_record_groups; two-shard msets record per shard.
        updates, mset, cross = item
        ops = ops_of(updates)
        ops.append(s.session_for(cl.shard_of(mset[0][0])).op_mset(mset))
        outcomes.extend(_row(o) for o in cl.update_batch(s, ops))
        for kvs in cross:
            outcomes.append(_row(cl.mset(s, kvs)))
        written.update(k for _kind, k, _v in updates)
        written.update(k for kvs in [mset] + cross for k, _v in kvs)

    B = wl.batch
    phase(f"load {wl.n_keys} keys",
          lambda lo: single_key_batch(
              [("set", k, v) for k, v in wl.load[lo:lo + B]]),
          range(0, wl.n_keys, B))
    phase(f"{len(wl.zipf)} zipf batches", single_key_batch, wl.zipf)
    phase(f"{len(wl.mixed)} MSET batches", mixed_batch, wl.mixed)
    cl.crash_master(wl.crash_shard)
    single_key_batch(wl.post_crash)
    reads = {}
    phase(f"read back {len(written)} keys",
          lambda k: reads.__setitem__(k, cl.read(s, s.op_get(k)).value),
          sorted(written))
    return dict(outcomes=outcomes, reads=reads,
                single_declines=single_declines,
                fused=dict(fused.stats) if fused else None,
                fast=sum(1 for o in outcomes if o[0]))


def _row(o):
    return (o.fast_path, o.synced_path, o.rtts, o.witness_accepts, o.value)


def check(dev: dict, ref: dict, wl: Workload) -> list:
    """Every disagreement between the device run, the Python reference run
    and the dict model (empty list = pass)."""
    errs = []
    if len(dev["outcomes"]) != len(ref["outcomes"]):
        errs.append(f"op count {len(dev['outcomes'])} != "
                    f"{len(ref['outcomes'])}")
    bad = [i for i, (a, b) in enumerate(zip(dev["outcomes"], ref["outcomes"]))
           if a != b]
    if bad:
        i = bad[0]
        errs.append(f"{len(bad)} per-op outcomes differ; first at op {i}: "
                    f"device {dev['outcomes'][i]} vs python "
                    f"{ref['outcomes'][i]}")
    model = wl.model()
    for name, run in (("device", dev), ("python", ref)):
        if set(run["reads"]) != set(model):
            errs.append(f"{name}: read back {len(run['reads'])} keys, "
                        f"{len(model)} were acknowledged")
        wrong = [k for k, v in run["reads"].items() if model.get(k) != v]
        if wrong:
            errs.append(f"{name}: {len(wrong)} keys read back a value other "
                        f"than their last acknowledged write ({wrong[:3]})")
    if dev["fused"]["fused_batches"] == 0:
        errs.append("no batch went through the fused device path")
    if dev["single_declines"]:
        errs.append(f"{dev['single_declines']} single-key batches declined "
                    "the fused path")
    if dev["fused"]["declined"] != len(wl.mixed):
        errs.append(f"{dev['fused']['declined']} batches declined, expected "
                    f"the {len(wl.mixed)} MSET batches")
    return errs


# ---------------------------------------------------------------------------
def device_line() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def compile_listener(stats: dict):
    """JAX monitoring listener summing trace + lower + compile seconds (and
    counting backend compiles) per main-path jitted impl."""
    def on_event(event, duration, **kw):
        name = kw.get("fun_name", "")
        name = name[4:-1] if name.startswith("jit(") else name
        if name in stats and event.startswith("/jax/core/compile/"):
            stats[name]["seconds"] += duration
            stats[name]["compiles"] += event.endswith(
                "backend_compile_duration")
    return on_event


def cache_listener(counts: dict):
    """JAX monitoring listener counting persistent-cache hits and misses
    (the backend-compile event above fires on a hit as well)."""
    def on_event(event, **kw):
        for kind in counts:
            if event == f"/jax/compilation_cache/cache_{kind}":
                counts[kind] += 1
    return on_event


def main(argv=None) -> int:
    args = parse_args(argv)
    t_start = time.perf_counter()
    dev = device_line()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              "no CPU or interpreter fallback", file=sys.stderr)
        return 1
    from jax import monitoring

    from repro.core.shard import SlotRouter
    from repro.jax_cache import enable_compile_cache
    from repro.kernels import dispatch_count, ops, reset_dispatch_count

    print(f"compile cache: {enable_compile_cache()}")
    stats = {n: {"compiles": 0, "seconds": 0.0} for n in MAIN_PATH_IMPLS}
    monitoring.register_event_duration_secs_listener(compile_listener(stats))
    cache = {"hits": 0, "misses": 0}
    monitoring.register_event_listener(cache_listener(cache))

    t = time.perf_counter()
    wl = Workload(args.seed, args.keys, BATCH, args.zipf_batches,
                  args.mset_batches, SlotRouter.uniform(N_SHARDS).shard_of)
    print(f"workload: {args.keys} keys x {VALUE_BYTES} B, "
          f"{args.zipf_batches} zipf + {args.mset_batches} MSET batches of "
          f"{BATCH}, seed {args.seed}: "
          f"{time.perf_counter() - t:.1f} s smoke time")
    reset_dispatch_count()
    dev_run = run_backend("device", wl)
    dispatches = dispatch_count()
    ref_run = run_backend("python", wl)

    for name, st in stats.items():
        print(f"compile {name}: {st['compiles']} compiles, "
              f"{st['seconds']:.2f} s")
    print(f"persistent compile cache: {cache['hits']} hits, "
          f"{cache['misses']} misses")
    fs = dev_run["fused"]
    print(f"fused batches {fs['fused_batches']}, fused ops {fs['fused_ops']}, "
          f"declined {fs['declined']}, kernels.dispatches {dispatches}")
    print(f"fast-path ops: device {dev_run['fast']}, python "
          f"{ref_run['fast']}, of {len(dev_run['outcomes'])}")
    errs = check(dev_run, ref_run, wl)
    for name in MAIN_PATH_IMPLS:
        if getattr(ops, name)._cache_size() == 0:
            errs.append(f"{name} never ran")
    print(f"smoke time {time.perf_counter() - t_start:.1f} s (not a metric)")
    for e in errs:
        print(f"FAIL: {e}")
    if errs:
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
