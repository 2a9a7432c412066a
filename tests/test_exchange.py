"""The URL exchange across shards: its own device scope and its counter
(core/stages.dispatch_exchange, coordination/metrics.py).

The contracts pinned here:
  * the compiled chunk names ``stage/dispatch/exchange``, and the
    ``all-to-all`` lies under it, on the fused and the unfused path;
  * ``dispatch_foreign`` counts the shipped URLs bound for another shard:
    a numpy count of the routed staging batch gives the same number, one
    shard reads 0 while it still ships, and the fused and unfused paths
    keep identical trajectories with it counted;
  * ``comm_ledger`` reports it as ``urls_crossed`` / ``crossed_per_page``.

The four-shard cases run once, in a subprocess with four virtual CPU
devices, and each test reads its part of what that run found.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api import CrawlSession
from repro.configs import get_reduced
from repro.configs.base import scaled
from repro.coordination import comm_ledger

FOUR_SHARDS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import json, re
    import jax
    import numpy as np
    from repro.api import CrawlSession
    from repro.api.session import chunk_program
    from repro.configs import get_reduced
    from repro.configs.base import scaled
    from repro.core import classifier as CLS
    from repro.core import crawler as CR
    from repro.core import stages as ST
    from repro.launch.mesh import make_host_mesh

    out = {}
    base = scaled(get_reduced("webparf"), ordering="opic_url",
                  link_pop_bias=1.0)
    mesh = make_host_mesh()
    n = mesh.devices.size
    FOREIGN = ST.SIDX["dispatch_foreign"]
    WALK = [ST.SIDX["dedup_tiles"], ST.SIDX["dedup_dense_tiles"]]

    crawlers, sessions = {}, {}
    for fused in (True, False):
        cfg = scaled(base, fused_dispatch=fused)
        tag = "fused" if fused else "unfused"
        # the compiled chunk: which ops carry the exchange scope
        sess = sessions[tag] = CrawlSession(cfg, mesh)
        hlo = chunk_program(cfg, mesh, axes=sess.axes).lower(
            sess.state).compile().as_text()
        a2a = [re.search(r'op_name="([^"]*)"', l)
               for l in hlo.splitlines() if " all-to-all(" in l]
        out[tag + "_a2a"] = [m.group(1) if m else "" for m in a2a]
        out[tag + "_scoped"] = sum("stage/dispatch/exchange/" in o for o in
                                   re.findall(r'op_name="([^"]*)"', hlo))
        # the eager trajectory over two dispatch intervals
        init, step_f, step_d = crawlers[tag] = CR.make_spmd_crawler(cfg,
                                                                   mesh)
        state, traj = init(), []
        for t in range(2 * cfg.dispatch_interval):
            fn = step_d if (t + 1) % cfg.dispatch_interval == 0 else step_f
            state = fn(state)[0]
            st = jax.device_get(state)
            traj.append([np.delete(np.asarray(x), WALK, axis=1).tolist()
                         if name == "stats" else np.asarray(x).tolist()
                         for name, x in zip(ST.CrawlState._fields, st)])
        out[tag + "_traj"] = traj
        out[tag + "_foreign"] = int(np.asarray(state.stats)[:, FOREIGN].sum())

    # one dispatch step, against a numpy count of its staging batch: the
    # fetch-only step runs the same stages before the dispatch would
    cfg = scaled(base, fused_dispatch=True)
    init, step_f, step_d = crawlers["fused"]
    state = init()
    for _ in range(cfg.dispatch_interval - 1):
        state = step_f(state)[0]
    staged = jax.device_get(step_f(state)[0])
    after = jax.device_get(step_d(state)[0])
    before = np.asarray(state.stats)
    delta = np.asarray(after.stats) - before
    r_local = cfg.n_slots // n
    want_foreign, want_sent = [], []
    for s in range(n):
        k = int(staged.staging_n[s])
        u = np.asarray(staged.staging_url[s][:k])
        src = np.asarray(staged.staging_src[s][:k])
        pred = np.asarray(CLS.predict_domain(
            u, src, cfg, step=int(state.step),
            accuracy=CLS.DEFAULT_ACCURACY))
        slot = np.asarray(staged.slot_of_domain)[
            np.clip(pred, 0, cfg.n_domains - 1)]
        dest = slot // r_local
        want_foreign.append(int((dest != s).sum()))
        want_sent.append(k)
    out["count"] = dict(
        foreign=delta[:, FOREIGN].tolist(), want_foreign=want_foreign,
        sent=delta[:, ST.SIDX["dispatch_sent"]].tolist(),
        want_sent=want_sent)

    # the ledger of a whole session
    rep = sessions["fused"].run(4 * cfg.dispatch_interval)
    out["ledger"] = dict(comm=rep.comm, stats=rep.stats, fetched=rep.fetched)
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    r = subprocess.run([sys.executable, "-c", FOUR_SHARDS],
                       capture_output=True, text=True, timeout=600, cwd=".")
    if r.returncode != 0:
        raise AssertionError(f"STDOUT:\n{r.stdout[-3000:]}\n"
                             f"STDERR:\n{r.stderr[-3000:]}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("path", ["fused", "unfused"])
def test_chunk_names_the_exchange_scope(four, path):
    a2a = four[path + "_a2a"]
    assert a2a, "the four-shard chunk has no all-to-all"
    assert all("stage/dispatch/exchange/" in o for o in a2a), a2a
    assert four[path + "_scoped"] > len(a2a)    # the packing and unpacking


def test_fused_and_unfused_trajectories_match_with_the_counter(four):
    from repro.core import stages as ST
    assert four["fused_foreign"] > 0
    for t, (a, b) in enumerate(zip(four["fused_traj"],
                                   four["unfused_traj"])):
        for name, x, y in zip(ST.CrawlState._fields, a, b):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"step {t}: CrawlState.{name} diverged")


def test_foreign_count_matches_numpy(four):
    c = four["count"]
    assert c["foreign"] == c["want_foreign"], c
    assert c["sent"] == c["want_sent"], c
    assert 0 < sum(c["foreign"]) < sum(c["sent"]), c


def test_ledger_reports_crossed_urls(four):
    led = four["ledger"]
    comm, stats = led["comm"], led["stats"]
    assert comm["urls_crossed"] == stats["dispatch_foreign"] > 0
    assert comm["urls_crossed"] < comm["urls_shipped"]
    assert comm["crossed_per_page"] == pytest.approx(
        comm["urls_crossed"] / led["fetched"])
    assert comm["crossed_per_page"] < comm["comm_per_page"]


def test_one_shard_crosses_nothing():
    cfg = scaled(get_reduced("webparf"), ordering="opic_url")
    rep = CrawlSession(cfg).run(2 * cfg.dispatch_interval)
    assert rep.stats["dispatch_sent"] > 0
    assert rep.stats["dispatch_foreign"] == 0
    assert rep.comm["urls_crossed"] == 0
    assert rep.comm["crossed_per_page"] == 0.0


def test_comm_ledger_fields():
    comm = comm_ledger({"dispatch_sent": 40, "dispatch_foreign": 30,
                        "dispatch_recv": 40}, fetched=10)
    assert comm["urls_shipped"] == 40 and comm["urls_crossed"] == 30
    assert comm["comm_per_page"] == 4.0
    assert comm["crossed_per_page"] == 3.0
    # a run that fetched nothing divides by one page
    assert comm_ledger({}, fetched=0)["crossed_per_page"] == 0.0
