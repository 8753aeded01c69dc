"""Span-layer invariants (DESIGN.md § 7.6):

* the device log2 bucket rule bit-matches the host twin (``bucket_of``)
  and ``span_record`` bit-matches a numpy oracle on random claim waves,
  including all-inactive waves (which must not perturb the plane);
* ``spans=None`` compiles each fused engine to the exact unspanned loop —
  spans on vs off is bit-identical on the acc, the queue planes, and
  every stats counter, for all four fused engines;
* the device sojourn histogram bit-matches a host FIFO replay of the
  fused round engine (every task counted once, at its true wait);
* birth stamps survive distqueue ticket wraparound across the int32
  boundary (the ``dist_queue_init(start=...)`` regime);
* per-class rows: ``class_of`` routes sojourns to the right histogram
  row with exact counts;
* export: ``write_jsonl(spans=...)`` round-trips the ``hist``/``flow``
  lines and both emitters pass ``tools/trace_check.py``, which also
  rejects empty-string stand-ins for numeric fields;
* the sojourn analyzers (percentiles, high-water, starvation flags) and
  the legacy-engine rejection contract.
"""

import collections
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.jaxcompat import make_mesh  # noqa: E402
from repro.obs import (  # noqa: E402
    Spans, Telemetry, bucket_edges, bucket_of, max_wait_highwater,
    read_jsonl, sojourn_percentiles, span_init, span_record, span_tick,
    starvation_flags, to_chrome_trace, write_chrome_trace, write_jsonl)
from repro.runtime import (  # noqa: E402
    MeshRoundRunner, PriorityMeshRoundRunner, PriorityRoundRunner,
    RoundRunner)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _mesh1():
    return make_mesh((1,), ("data",))


def _tree_step():
    def step(acc, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 32))[:, None]
        return acc, cv, cm
    return step


def _pri_step():
    def step(acc, keys, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        ck = jnp.stack([keys + 1, keys + 2], -1).astype(jnp.int32)
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 32))[:, None]
        return acc, ck, cv, cm
    return step


def _pri_mesh_tree_step():
    def step(acc, keys, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        ck = (cv * 7919) % 1000
        cm = (valid & (vals < 32))[:, None]
        return acc, ck, cv, cm
    return step


def _assert_identical(res_off, res_on):
    (acc0, st0, stats0), (acc1, st1, stats1) = res_off, res_on
    np.testing.assert_array_equal(np.asarray(acc0), np.asarray(acc1))
    for a, b in zip(jax.tree.leaves(st0), jax.tree.leaves(st1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats0 == stats1


# -- the device bucket rule and span_record vs a numpy oracle -----------------


@pytest.mark.parametrize("buckets", [2, 8, 16])
def test_bucket_rule_device_matches_host(buckets):
    sojourns = np.concatenate([np.arange(200),
                               [2 ** 10, 2 ** 20, 2 ** 30, 2 ** 31 - 1]])
    sp = span_init(1, buckets=buckets, flow_capacity=1,
                   lanes=len(sojourns))
    sp = span_record(sp, np.zeros(len(sojourns), np.int32),
                     sojourns.astype(np.int32),
                     np.ones(len(sojourns), bool),
                     np.arange(len(sojourns), dtype=np.int32))
    want = np.bincount([bucket_of(s, buckets) for s in sojourns],
                       minlength=buckets)
    # lane-major device plane: counts fold across lanes, max-wait is the
    # trailing column
    acc = np.asarray(sp.hist)
    np.testing.assert_array_equal(acc[:, 0, :buckets].sum(0), want)
    assert int(acc[:, 0, buckets].max()) == 2 ** 31 - 1
    # edges bracket their bucket: bucket_of(edge) == that bucket
    for b, e in enumerate(bucket_edges(buckets)):
        assert bucket_of(int(e), buckets) == b


def test_span_record_matches_numpy_oracle_random():
    rng = np.random.default_rng(7)
    k, nb, f, b = 3, 8, 16, 11
    sp = span_init(k, buckets=nb, flow_capacity=f, lanes=b)
    hist = np.zeros((k, nb), np.int64)
    maxw = np.zeros((k,), np.int64)
    flows = []
    rnd = 0
    for _ in range(20):
        cls = rng.integers(0, k, b).astype(np.int32)
        s = rng.integers(0, 300, b).astype(np.int32)
        valid = rng.random(b) < 0.6
        sp = span_record(sp, cls, s, valid, np.arange(b, dtype=np.int32))
        sp = span_tick(sp)
        for c, w, v in zip(cls, s, valid):
            if v:
                hist[c, bucket_of(int(w), nb)] += 1
                maxw[c] = max(maxw[c], int(w))
        # flow ring samples ONE exemplar per recorded round: lane 0's
        # lifecycle, whenever lane 0 claimed (ref is lane index = 0)
        if valid[0]:
            flows.append((rnd - int(s[0]), rnd, int(cls[0]), 0))
        rnd += 1
    acc = np.asarray(sp.hist)
    np.testing.assert_array_equal(acc[..., :nb].sum(0), hist)
    np.testing.assert_array_equal(acc[..., nb].max(0), maxw)
    assert int(sp.fcount) == len(flows)
    assert int(sp.round) == rnd
    # ring keeps the newest min(f, written) exemplars, in write order
    keep = min(len(flows), f)
    kept = flows[len(flows) - keep:]
    got = np.asarray(sp.flows)[
        np.arange(len(flows) - keep, len(flows)) % f]
    np.testing.assert_array_equal(got, np.asarray(kept))


def test_span_record_all_inactive_wave_no_change():
    sp = span_init(2, buckets=8, flow_capacity=4, lanes=2)
    sp = span_record(sp, jnp.array([0, 1]), jnp.array([3, 5]),
                     jnp.array([True, True]), jnp.array([9, 9]))
    before = jax.tree.map(np.asarray, sp)
    sp2 = span_record(sp, jnp.array([0, 1]), jnp.array([7, 7]),
                      jnp.array([False, False]), jnp.array([9, 9]))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(sp2)):
        np.testing.assert_array_equal(a, np.asarray(b))


# -- spans=None bit-identity on all four fused engines ------------------------


def _run_engine(name, sp, mesh):
    if name == "rounds":
        r = RoundRunner(_tree_step(), capacity_log2=8, batch=16, spans=sp)
        acc, st = r.run([1], acc=jnp.zeros(80, jnp.int32))
    elif name == "prounds":
        r = PriorityRoundRunner(_pri_step(), capacity_log2=8, batch=16,
                                spans=sp)
        acc, st = r.run([5], [1], acc=jnp.zeros(80, jnp.int32))
    elif name == "mesh":
        r = MeshRoundRunner(_tree_step(), mesh=mesh, capacity_log2=8,
                            batch=16, combine=lambda a: a.sum(0), spans=sp)
        acc, st = r.run([1], acc=jnp.zeros(80, jnp.int32))
    else:
        r = PriorityMeshRoundRunner(_pri_mesh_tree_step(), mesh=mesh,
                                    capacity_log2=8, batch=16,
                                    relaxed=(name == "pmesh-relaxed"),
                                    combine=lambda a: a.sum(0), spans=sp)
        acc, st = r.run([7919 % 1000], [1], acc=jnp.zeros(80, jnp.int32))
    return (acc, st, dict(r.stats))


@pytest.mark.parametrize("name", ["rounds", "prounds", "mesh",
                                  "pmesh-relaxed", "pmesh-strict"])
def test_spans_off_bit_identical(name):
    mesh = _mesh1()
    off = _run_engine(name, None, mesh)
    sp = Spans(classes=1, engine=name)
    on = _run_engine(name, sp, mesh)
    _assert_identical(off, on)
    assert sp.total == on[2]["processed"]   # one sojourn per task
    assert sp.percentile(0.99) is not None
    # the body is claim → step → publish, so no child turns around in the
    # round it was born: every non-seed waits >= 1 round, and the engine
    # final round always claims something (quiescence) — histogram mass
    # beyond bucket 0 is guaranteed on a multi-round tree
    assert on[2]["rounds"] > 1
    assert int(sp.hist[:, 1:].sum()) > 0


# -- device histogram vs host FIFO replay -------------------------------------


def test_fused_rounds_histogram_matches_host_replay():
    batch = 16
    sp = Spans(classes=1, engine="rounds")
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=batch, spans=sp)
    r.run([1], acc=jnp.zeros(80, jnp.int32))
    # host replay of the FIFO megaround: claim min(batch, size) oldest,
    # record sojourn, append children (vals < 32 spawn 2v, 2v+1) at birth
    # round = the claiming round
    q = collections.deque([(1, 0)])
    hist = np.zeros((1, sp.buckets), np.int64)
    maxw = np.zeros((1,), np.int64)
    rnd = 0
    while q:
        wave = [q.popleft() for _ in range(min(batch, len(q)))]
        for v, born in wave:
            s = rnd - born
            hist[0, bucket_of(s, sp.buckets)] += 1
            maxw[0] = max(maxw[0], s)
        for v, _ in wave:
            if v < 32:
                q.append((2 * v, rnd))
                q.append((2 * v + 1, rnd))
        rnd += 1
    assert r.stats["rounds"] == rnd
    np.testing.assert_array_equal(sp.hist, hist)
    np.testing.assert_array_equal(sp.max_wait, maxw)


def test_priority_class_rows_exact():
    # batch=1 over two inert seeds: key 3 (class 0) pops in round 0 with
    # sojourn 0, key 100 (class 1) pops in round 1 with sojourn 1
    def inert(acc, keys, vals, valid):
        z = jnp.zeros((keys.shape[0], 1), jnp.int32)
        return acc + valid.sum(), z, z, z.astype(bool)

    sp = Spans(classes=2, engine="pr", class_of=lambda k: k // 64)
    r = PriorityRoundRunner(inert, capacity_log2=4, batch=1, spans=sp)
    r.run([3, 100], [7, 8], acc=jnp.int32(0))
    np.testing.assert_array_equal(
        sp.hist, [[1] + [0] * (sp.buckets - 1),
                  [0, 1] + [0] * (sp.buckets - 2)])
    np.testing.assert_array_equal(sp.max_wait, [0, 1])
    assert [(f["birth"], f["claim"], f["cls"]) for f in sp.flows] == \
        [(0, 0, 0), (0, 1, 1)]


# -- ticket wraparound across the int32 boundary ------------------------------


def test_birth_stamps_survive_ticket_wraparound():
    from jax.sharding import PartitionSpec as P
    from repro.core.distqueue import (dist_claim_round, dist_publish_round,
                                      dist_queue_init)
    mesh = _mesh1()
    cap = 64                      # n2 = 128 physical slots
    state = dist_queue_init(cap, start=(2 ** 31 - 128))
    births = jnp.zeros((128,), jnp.int32)
    b = 48

    def inner(state, births):
        vals = jnp.arange(b, dtype=jnp.int32) + 100
        mask = jnp.ones((b,), jnp.int32)
        bouts = []
        # round 1's tickets cross 2**31 (tail starts 128 below, round 0
        # advances it 48): stamps must read back across the wrap
        for r in range(2):
            pr = dist_publish_round(state, vals, mask, "data", capacity=cap,
                                    births=births,
                                    birth_round=jnp.int32(r + 5))
            state, births = pr[0], pr[4]
            cr = dist_claim_round(state, jnp.int32(b), b, "data",
                                  births=births)
            state, ok, bout = cr[0], cr[2], cr[3]
            bouts.append((ok, bout))
        return bouts[0] + bouts[1]

    f = jax.jit(jax.shard_map(inner, mesh=mesh,
                              in_specs=(P(), P()),
                              out_specs=(P(), P(), P(), P()),
                              check_vma=False))
    ok0, b0, ok1, b1 = f(state, births)
    assert bool(np.asarray(ok0).all()) and bool(np.asarray(ok1).all())
    np.testing.assert_array_equal(np.asarray(b0), np.full(b, 5))
    np.testing.assert_array_equal(np.asarray(b1), np.full(b, 6))


# -- 2-shard forced-device parity + merge -------------------------------------


_TWO_SHARD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys; sys.path.insert(0, {src!r})
import numpy as np
import jax, jax.numpy as jnp
from repro.jaxcompat import make_mesh
from repro.obs import Spans
from repro.runtime import MeshRoundRunner, PriorityMeshRoundRunner

mesh = make_mesh((2,), ("data",))

def tree_step(acc, vals, valid):
    acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
    cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
    cm = (valid & (vals < 32))[:, None]
    return acc, cv, cm

def pri_step(acc, keys, vals, valid):
    acc, cv, cm = tree_step(acc, vals, valid)
    ck = (cv * 7919) % 1000
    return acc, ck, cv, cm

def check(mk_runner, run_args, engine):
    out = []
    for sp in (None, Spans(classes=2, engine=engine)):
        r = mk_runner(sp)
        acc, st = r.run(*run_args, acc=jnp.zeros(80, jnp.int32))
        out.append((np.asarray(acc), jax.tree.leaves(st), dict(r.stats)))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    for a, b in zip(out[0][1], out[1][1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert out[0][2] == out[1][2]
    # the sharded planes merged at drain: mass == processed, 2 rows
    assert sp.total == out[1][2]["processed"], engine
    assert sp.hist.shape[0] == 2, engine
    return sp

sp = check(lambda sp: MeshRoundRunner(
    tree_step, mesh=mesh, capacity_log2=8, batch=16,
    combine=lambda a: a.sum(0), spans=sp), ([1],), "mesh")
assert all(r.sum() > 0 for r in sp.hist)     # both shards claimed work

for relaxed in (True, False):
    check(lambda sp: PriorityMeshRoundRunner(
        pri_step, mesh=mesh, capacity_log2=8, batch=16, relaxed=relaxed,
        combine=lambda a: a.sum(0), spans=sp),
        ([7919 % 1000], [1]), "pmesh")
print("TWO_SHARD_SPANS_OK")
"""


def test_two_shard_mesh_spans_bit_identical():
    """Forced-device acceptance: spans on vs off is bit-identical on the
    mesh engines at 2 shards, and the sharded span planes merge to
    exactly one sojourn per processed task (the strict mode's local-slice
    recording must not double-count the replicated heap)."""
    src = os.path.join(REPO, "src")
    res = subprocess.run(
        [sys.executable, "-c", _TWO_SHARD_SCRIPT.format(src=src)],
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TWO_SHARD_SPANS_OK" in res.stdout


# -- export / trace_check -----------------------------------------------------


def test_span_export_roundtrip_and_trace_check(tmp_path):
    tel = Telemetry(256, engine="rounds")
    sp = Spans(classes=1, engine="rounds")
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=16,
                    telemetry=tel, spans=sp)
    r.run([1], acc=jnp.zeros(80, jnp.int32))
    path = str(tmp_path / "trace.jsonl")
    n = write_jsonl(path, tel.records, tel.sync_points,
                    metrics=tel.registry.snapshot(), engine="rounds",
                    spans=sp)
    assert n == 1 + len(tel.records) + len(tel.sync_points) + 1 \
        + 1 + len(sp.flows)
    back = read_jsonl(path)
    want = dict(sp.summary())
    want["engine"] = "rounds"
    assert back["hist"] == want
    assert back["flows"] == [{"engine": "rounds", **f} for f in sp.flows]
    # chrome flow events: one s/f pair per sampled lifecycle
    trace = to_chrome_trace(tel.records, tel.sync_points, engine="rounds",
                            flows=sp.flows)
    sev = [e for e in trace["traceEvents"] if e["ph"] == "s"]
    fev = [e for e in trace["traceEvents"] if e["ph"] == "f"]
    assert len(sev) == len(fev) == len(sp.flows)
    assert all(e["bp"] == "e" for e in fev)
    chrome = str(tmp_path / "trace.json")
    write_chrome_trace(chrome, tel.records, tel.sync_points,
                       engine="rounds", flows=sp.flows)
    tool = os.path.join(REPO, "tools", "trace_check.py")
    ok = subprocess.run([sys.executable, tool, path, "--chrome", chrome],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr


def test_trace_check_rejects_empty_string_numerics(tmp_path):
    tel = Telemetry(256, engine="rounds")
    sp = Spans(classes=1, engine="rounds")
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=16,
                    telemetry=tel, spans=sp)
    r.run([1], acc=jnp.zeros(80, jnp.int32))
    good = str(tmp_path / "good.jsonl")
    write_jsonl(good, tel.records, tel.sync_points, engine="rounds",
                spans=sp)
    tool = os.path.join(REPO, "tools", "trace_check.py")
    import json
    lines = [json.loads(ln) for ln in open(good)]
    # "" where a number belongs (the bench_obs overhead_pct pathology)
    for field, kind in (("total", "hist"), ("birth", "flow")):
        bad = str(tmp_path / f"bad_{field}.jsonl")
        with open(bad, "w") as f:
            for d in lines:
                d = dict(d)
                if d["kind"] == kind:
                    d[field] = ""
                f.write(json.dumps(d) + "\n")
        res = subprocess.run([sys.executable, tool, bad],
                             capture_output=True, text=True)
        assert res.returncode == 1 and "empty-string" in res.stderr, field
    # a hist line whose counts disagree with total is also rejected
    bad = str(tmp_path / "bad_sum.jsonl")
    with open(bad, "w") as f:
        for d in lines:
            d = dict(d)
            if d["kind"] == "hist":
                d["total"] = d["total"] + 1
            f.write(json.dumps(d) + "\n")
    res = subprocess.run([sys.executable, tool, bad],
                         capture_output=True, text=True)
    assert res.returncode == 1 and "sum" in res.stderr


# -- analyzers ----------------------------------------------------------------


def _summary(hist, maxw):
    hist = np.asarray(hist)
    return {"classes": hist.shape[0], "buckets": hist.shape[1],
            "bucket_edges": bucket_edges(hist.shape[1]).tolist(),
            "hist": hist.tolist(), "max_wait": list(maxw),
            "total": int(hist.sum()), "p50": None, "p95": None, "p99": None}


def test_sojourn_percentiles_from_summary():
    # class 0: 10 sojourns in bucket 1 (edge 1); class 1: 1 in bucket 3;
    # CDF(bucket 1) = 10/11 < 0.95, so p95 spills into the last bucket
    s = _summary([[0, 10, 0, 0], [0, 0, 0, 1]], [1, 7])
    assert sojourn_percentiles(s) == {"p50": 1, "p95": 7, "p99": 7}
    assert sojourn_percentiles(s, cls=1) == {"p50": 7, "p95": 7, "p99": 7}
    assert sojourn_percentiles(_summary(np.zeros((1, 4)), [0])) == \
        {"p50": None, "p95": None, "p99": None}


def test_max_wait_highwater_and_starvation():
    s = _summary([[50, 50, 0, 0], [0, 0, 0, 2]], [1, 900])
    hw = max_wait_highwater(s)
    assert hw == {"per_class": [1, 900], "worst_class": 1,
                  "high_water": 900}
    fl = starvation_flags(s, factor=8.0)
    assert fl["starved_classes"] == [1]          # 900 > 8 * p50(=1)
    assert fl["per_class"][0]["starved"] is False
    # fabric cross-check compares direction only (class 0 = urgent)
    agree = starvation_flags(
        s, wait_stats={"urgent_max_wait": 10.0, "normal_max_wait": 5000.0})
    assert agree["fabric"]["agrees"] is True
    disagree = starvation_flags(
        s, wait_stats={"urgent_max_wait": 5000.0, "normal_max_wait": 10.0})
    assert disagree["fabric"]["agrees"] is False


# -- API contracts ------------------------------------------------------------


def test_legacy_engines_reject_spans():
    sp = Spans(classes=1)
    with pytest.raises(ValueError, match="fused"):
        RoundRunner(_tree_step(), fused=False, spans=sp)
    with pytest.raises(ValueError, match="fused"):
        PriorityRoundRunner(_pri_step(), fused=False, spans=sp)
    with pytest.raises(ValueError, match="fused"):
        MeshRoundRunner(_tree_step(), mesh=_mesh1(), fused=False,
                        combine=lambda a: a.sum(0), spans=sp)
    with pytest.raises(ValueError, match="fused"):
        PriorityMeshRoundRunner(_pri_mesh_tree_step(), mesh=_mesh1(),
                                fused=False, combine=lambda a: a.sum(0),
                                spans=sp)


def test_spans_validation_and_multi_run_banking():
    with pytest.raises(ValueError, match="classes"):
        Spans(classes=0)
    with pytest.raises(ValueError, match="buckets"):
        Spans(buckets=1)
    with pytest.raises(ValueError, match="flow_capacity"):
        Spans(flow_capacity=0)
    sp = Spans(classes=1, engine="rounds")
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=16, spans=sp)
    r.run([1], acc=jnp.zeros(80, jnp.int32))
    one = sp.total
    r.run([1], acc=jnp.zeros(80, jnp.int32))   # second run banks the first
    assert sp.total == 2 * one
    assert sp.registry.get("rounds.sojourn_p99") is not None
