"""Multi-host control plane over REAL OS processes (round-3 verdict item 1:
'election/transport never connected to a second process').

Reference: discovery/zen/ZenDiscovery.java — join/publish/leave + fault
detection. A master (rank 0) in this process and a rank-1 member in a
separate Python process talk over the TCP transport; membership, election,
graceful leave, and ping-failure reaping are asserted against the master's
published cluster state. jax.distributed.initialize runs in a subprocess
(it must precede any JAX computation, which the test process already did).
"""
import socket
import subprocess
import sys
import time

import pytest

from elasticsearch_tpu.cluster.bootstrap import MultiHostCluster
from elasticsearch_tpu.node import Node


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


from tests.integration.multihost_util import member_code as _member_code


def _wait(predicate, timeout=10.0, step=0.05):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if predicate():
            return True
        time.sleep(step)
    return False


@pytest.fixture()
def master():
    node = Node(name="rank0")
    c = MultiHostCluster(node, rank=0, world=2, transport_port=_free_port(),
                         ping_interval=0.2, ping_retries=2,
                         minimum_master_nodes=1)
    yield node, c
    c.close()
    node.close()


def _spawn_rank1(port: int) -> subprocess.Popen:
    p = subprocess.Popen([sys.executable, "-c", _member_code(port)],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True)
    line = p.stdout.readline()
    assert "JOINED" in line, line
    return p


def test_join_election_and_graceful_leave(master):
    node, c = master
    port = c.master_addr[1]
    assert c.is_master
    p = _spawn_rank1(port)
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        ids = sorted(node.cluster_state.nodes)
        assert node.cluster_state.master_node_id == ids[0]
        assert ids[0].startswith("0000-") and ids[1].startswith("0001-")
        # graceful leave removes the member
        p.stdin.write("leave\n")
        p.stdin.flush()
        assert "LEFT" in p.stdout.readline()
        assert _wait(lambda: len(node.cluster_state.nodes) == 1)
        assert c.is_master
    finally:
        p.kill()
        p.wait()


def test_fault_detection_reaps_dead_process(master):
    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    assert _wait(lambda: len(node.cluster_state.nodes) == 2)
    p.kill()  # hard death: no leave message — only pings can find out
    p.wait()
    assert _wait(lambda: len(node.cluster_state.nodes) == 1, timeout=15.0), \
        node.cluster_state.nodes
    assert c.is_master


def test_cross_host_query_then_fetch(master):
    """The data plane (round-4 verdict missing #2): two processes each own
    one shard of a 2-shard index; routed writes land on the owner, and a
    search via rank-0 scatters the query phase, merges, and fetches across
    the process boundary — results oracle-checked against a single-process
    node with the identical shard layout.

    Reference: action/search/type/TransportSearchQueryThenFetchAction.java
    (scatter/merge/fetch), action/index/TransportIndexAction.java (routed
    write)."""
    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        idx_body = {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {
                "body": {"type": "text"},
                "grp": {"type": "keyword"},
                "n": {"type": "integer"}}},
        }
        c.data.create_index("events", idx_body)
        assig = c.dist_indices["events"]["assignment"]
        # truly split across hosts (single-copy shards, one per node)
        assert len({owners[0] for owners in assig.values()}) == 2, assig

        docs = {}
        for i in range(40):
            src = {"body": f"alpha beta {'gamma' if i % 3 == 0 else 'delta'} tok{i}",
                   "grp": "even" if i % 2 == 0 else "odd", "n": i}
            r = c.data.index_doc("events", str(i), src)
            assert r["result"] == "created", r
            docs[str(i)] = src
        c.data.refresh("events")

        # the remote process REALLY holds one shard: the coordinator's own
        # engines hold only a strict subset (Node.search itself now
        # scatters cross-host, so read the local copies directly)
        local_total = sum(sh.engine.num_docs
                          for sh in node.indices["events"].shards)
        assert 0 < local_total < 40, local_total

        # routed point reads cross the boundary too
        for i in ("0", "17", "33"):
            g = c.data.get_doc("events", i)
            assert g["found"] and g["_source"] == docs[i], g

        oracle = Node(name="oracle")
        oracle.create_index("events", idx_body)
        for i, src in docs.items():
            oracle.indices["events"].index_doc(i, src)
        oracle.indices["events"].refresh()

        bodies = [
            {"query": {"match": {"body": "gamma"}}, "size": 20},
            {"query": {"bool": {"filter": {"range": {"n": {"gte": 30}}}}},
             "sort": [{"n": "desc"}], "size": 5},
            {"query": {"match_all": {}}, "size": 0,
             "aggs": {"groups": {"terms": {"field": "grp"},
                                 "aggs": {"mean_n": {"avg": {"field": "n"}}}}}},
        ]
        for body in bodies:
            got = c.data.search("events", body)
            want = oracle.search("events", body)
            assert got["hits"]["total"] == want["hits"]["total"], body
            got_scores = {h["_id"]: h["_score"] for h in got["hits"]["hits"]}
            want_scores = {h["_id"]: h["_score"] for h in want["hits"]["hits"]}
            assert set(got_scores) == set(want_scores), body
            for k, v in want_scores.items():
                if v is None:
                    assert got_scores[k] is None
                else:
                    assert got_scores[k] == pytest.approx(v, rel=1e-4)
            if "aggs" in body:
                assert got["aggregations"] == want["aggregations"]
        # the sorted query's ORDER must agree exactly (deterministic keys)
        got = c.data.search("events", bodies[1])
        want = oracle.search("events", bodies[1])
        assert [h["_id"] for h in got["hits"]["hits"]] == \
               [h["_id"] for h in want["hits"]["hits"]]
        oracle.close()
    finally:
        p.kill()
        p.wait()


def test_replica_promotion_survives_node_death(master):
    """Round-4 verdict missing #4 (half 1): with number_of_replicas=1 every
    write fans out to a cross-host copy; killing the process that owns a
    primary promotes the survivor's copy, and search stays correct with
    zero failed shards. Reference: TransportShardReplicationOperation-
    Action (primary→replica hop) + RoutingNodes promotion."""
    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        c.data.create_index("rep", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "n": {"type": "integer"}}}})
        assig = c.dist_indices["rep"]["assignment"]
        assert all(len(owners) == 2 for owners in assig.values()), assig
        primaries = {owners[0] for owners in assig.values()}
        assert len(primaries) == 2, assig  # each node primaries one shard
        for i in range(40):
            c.data.index_doc("rep", str(i), {"body": f"word tok{i}", "n": i})
        c.data.refresh("rep")
        r = c.data.search("rep", {"query": {"match_all": {}}, "size": 0})
        assert r["hits"]["total"] == 40

        p.kill()  # hard death of one primary's owner
        p.wait()
        assert _wait(lambda: len(node.cluster_state.nodes) == 1, timeout=15.0)
        assert _wait(lambda: all(
            len(o) == 1 and o[0] == c.local.node_id
            for o in c.dist_indices["rep"]["assignment"].values()),
            timeout=10.0), c.dist_indices["rep"]["assignment"]

        r = c.data.search("rep", {"query": {"match_all": {}}, "size": 50})
        assert r["hits"]["total"] == 40, r["hits"]["total"]
        assert r["_shards"]["failed"] == 0, r["_shards"]
        assert {h["_id"] for h in r["hits"]["hits"]} == \
               {str(i) for i in range(40)}
        # the promoted copy serves routed reads too
        g = c.data.get_doc("rep", "7")
        assert g["found"] and g["_source"]["n"] == 7
    finally:
        p.kill()
        p.wait()


def test_join_triggers_shard_recovery_stream(master):
    """Round-4 verdict missing #4 (half 2): a node joining an
    under-replicated cluster pulls each assigned shard's live docs from
    the surviving copy (ops-based RecoverySourceHandler phase 1+2) and
    activates it. Verified by querying the NEW node's shards directly
    over the transport."""
    from elasticsearch_tpu.cluster.search_action import ACTION_QUERY

    node, c = master
    # alone in the cluster: replicas stay unassigned
    c.data.create_index("solo", {
        "settings": {"number_of_shards": 2, "number_of_replicas": 1},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    for i in range(30):
        c.data.index_doc("solo", str(i), {"body": f"alpha tok{i}"})
    c.data.refresh("solo")
    assert all(len(o) == 1 for o in
               c.dist_indices["solo"]["assignment"].values())

    p = _spawn_rank1(c.master_addr[1])
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        # reconcile assigned the new node as replica of both shards
        assert _wait(lambda: all(
            len(o) == 2 for o in
            c.dist_indices["solo"]["assignment"].values()), timeout=10.0)
        rank1 = next(nid for nid in node.cluster_state.nodes
                     if nid != c.local.node_id)

        def _rank1_docs():
            try:
                res = c.data._send(rank1, ACTION_QUERY, {
                    "index": "solo", "shards": [0, 1],
                    "body": {"query": {"match_all": {}}, "size": 0}})
            except Exception:
                return -1
            return sum(sh["total"] for sh in res["shards"])

        # the recovery stream runs async after the join — poll until the
        # new node's OWN shards serve all 30 docs
        assert _wait(lambda: _rank1_docs() == 30, timeout=20.0), \
            _rank1_docs()
    finally:
        p.kill()
        p.wait()


def test_rest_routes_through_cross_host_data_plane(master):
    """`--coordinator` mode end-to-end: REST operations on a distributed
    index route through the data plane — create computes the assignment
    on the master, writes land on shard-owner processes, GET/DELETE are
    hash-routed, and search scatters the query phase cross-host."""
    import json
    import urllib.request

    from elasticsearch_tpu.rest.server import RestServer

    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    srv = RestServer(node, port=0)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        r = urllib.request.Request(
            base + path, method=method,
            data=json.dumps(body).encode() if body is not None else None)
        try:
            with urllib.request.urlopen(r) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        st, r = req("PUT", "/revents", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        assert st == 200 and r["acknowledged"], r
        owners = {o[0] for o in
                  c.dist_indices["revents"]["assignment"].values()}
        assert len(owners) == 2  # really split across the two processes
        for i in range(20):
            st, r = req("PUT", f"/revents/t/{i}",
                        {"body": f"alpha tok{i}"})
            assert st in (200, 201) and r["result"] == "created", r
        st, _ = req("POST", "/revents/_refresh")
        assert st == 200
        # a doc on the REMOTE shard is readable and deletable over REST
        from elasticsearch_tpu.cluster.routing import shard_id_for

        remote_id = next(
            str(i) for i in range(20)
            if c.data.owner_of("revents", shard_id_for(str(i), 2))
            != c.local.node_id)
        st, g = req("GET", f"/revents/t/{remote_id}")
        assert st == 200 and g["found"], g
        st, r = req("POST", "/revents/_search",
                    {"query": {"match": {"body": "alpha"}}, "size": 25})
        assert st == 200 and r["hits"]["total"] == 20, r["hits"]["total"]
        assert r["_shards"] == {"total": 2, "successful": 2, "failed": 0}
        st, d = req("DELETE", f"/revents/t/{remote_id}?refresh=true")
        assert st == 200 and d["result"] == "deleted", d
        st, r = req("POST", "/revents/_search",
                    {"query": {"match_all": {}}, "size": 25})
        assert r["hits"]["total"] == 19
        assert remote_id not in {h["_id"] for h in r["hits"]["hits"]}
        # typed search, count, update, and bulk all route cross-host too
        st, r = req("POST", "/revents/t/_search",
                    {"query": {"match_all": {}}, "size": 0})
        assert r["hits"]["total"] == 19, r["hits"]["total"]
        st, r = req("GET", "/revents/_count")
        assert r["count"] == 19, r
        other_remote = next(
            str(i) for i in range(20)
            if str(i) != remote_id
            and c.data.owner_of("revents", shard_id_for(str(i), 2))
            != c.local.node_id)
        st, r = req("POST", f"/revents/t/{other_remote}/_update",
                    {"doc": {"body": "updated zeta"}})
        assert st == 200 and r["result"] == "updated", r
        st, g = req("GET", f"/revents/t/{other_remote}")
        assert g["_source"]["body"] == "updated zeta", g
        ndjson = (json.dumps({"index": {"_index": "revents", "_type": "t",
                                        "_id": "b1"}})
                  + "\n" + json.dumps({"body": "bulk doc"}) + "\n")
        breq = urllib.request.Request(base + "/_bulk", method="POST",
                                      data=ndjson.encode())
        with urllib.request.urlopen(breq) as resp:
            br = json.loads(resp.read())
        assert not br["errors"], br
        st, g = req("GET", "/revents/t/b1")
        assert st == 200 and g["found"], g
        st, _ = req("POST", "/revents/_refresh")

        # msearch on a dist index must NOT take the local fused batch
        # (it would see only local shards): totals must be cluster-wide
        mlines = ""
        for _ in range(3):
            mlines += json.dumps({"index": "revents"}) + "\n"
            mlines += json.dumps({"query": {"match_all": {}},
                                  "size": 0}) + "\n"
        mreq = urllib.request.Request(base + "/_msearch", method="POST",
                                      data=mlines.encode())
        with urllib.request.urlopen(mreq) as resp:
            mr = json.loads(resp.read())
        assert all(r["hits"]["total"] == 20 for r in mr["responses"]), \
            [r["hits"]["total"] for r in mr["responses"]]

        # update_by_query (script) touches docs on BOTH processes
        st, r = req("POST", "/revents/_update_by_query", {
            "query": {"match_all": {}},
            "script": {"inline": "ctx._source.touched = 1"}})
        assert st == 200 and r["updated"] == 20, r
        assert r["total"] == 20 and not r["failures"], r
        st, g = req("GET", f"/revents/t/{other_remote}")
        assert g["_source"].get("touched") == 1, g

        # delete_by_query removes docs cluster-wide
        st, r = req("POST", "/revents/_delete_by_query",
                    {"query": {"match_all": {}}})
        assert st == 200 and r["deleted"] == 20, r
        st, r = req("POST", "/revents/_search",
                    {"query": {"match_all": {}}, "size": 5})
        assert r["hits"]["total"] == 0, r["hits"]["total"]
    finally:
        srv.stop()
        p.kill()
        p.wait()


def test_snapshot_restore_across_hosts(master, tmp_path):
    """Round-4 verdict missing #6: snapshot a distributed index (each
    shard's owner writes its own blobs into the shared repository) and
    restore it INTO the multi-host cluster — the master computes a fresh
    cross-host assignment and every assigned copy replays its shard from
    the repo. Reference: snapshots/SnapshotsService.java (data nodes
    write shard blobs), snapshots/RestoreService.java:1-120 (master
    computes restore routing; data nodes recover from the repo)."""
    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    repo = str(tmp_path / "repo")
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        c.data.create_index("snap_src", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "n": {"type": "integer"}}}})
        assig = c.dist_indices["snap_src"]["assignment"]
        assert len({o[0] for o in assig.values()}) == 2, assig
        # an alias must survive the round trip AND resolve on every
        # process after restore (it rides the published dist metadata)
        node.indices["snap_src"].aliases["snap_alias"] = {}
        docs = {}
        for i in range(30):
            src = {"body": f"alpha {'beta' if i % 2 else 'gamma'} tok{i}",
                   "n": i}
            c.data.index_doc("snap_src", str(i), src)
            docs[str(i)] = src
        c.data.refresh("snap_src")

        r = c.data.create_snapshot(repo, "snap1")
        assert r["snapshot"]["state"] == "SUCCESS", r
        assert r["snapshot"]["shards"]["failed"] == 0, r
        # the manifest really contains BOTH shards' docs (the remote
        # owner's blobs landed in the shared repo, not just local ones)
        from elasticsearch_tpu.index.snapshots import FsRepository

        fs = FsRepository("check", repo)
        m = fs.get_manifest("snap1")
        n_docs = sum(len(fs.get_blob(sha)["docs"])
                     for sh in m["indices"]["snap_src"]["shards"]
                     for sha in sh["blobs"])
        assert n_docs == 30, n_docs

        # restore under a new name: shards spread across BOTH processes
        r = c.data.restore_snapshot(repo, "snap1",
                                    rename_pattern="snap_src",
                                    rename_replacement="snap_dst")
        assert r["snapshot"]["indices"] == ["snap_dst"], r
        assert r["snapshot"]["shards"]["failed"] == 0, r
        assig = c.dist_indices["snap_dst"]["assignment"]
        assert len({o[0] for o in assig.values()}) == 2, assig
        # the cross-host replica count survived the manifest round trip:
        # every restored shard came back with a primary AND a replica,
        # and restore left no copy stuck in INITIALIZING
        assert all(len(o) == 2 for o in assig.values()), assig
        assert all(not v for v in
                   c.dist_indices["snap_dst"]["initializing"].values())

        got = c.data.search("snap_dst",
                            {"query": {"match": {"body": "gamma"}},
                             "size": 30})
        assert got["hits"]["total"] == 15, got["hits"]["total"]
        assert got["_shards"]["failed"] == 0, got["_shards"]
        # the restored alias rides the published metadata and scatters
        # cross-host: drop the original's copy so it resolves uniquely,
        # then search THROUGH the alias via the data plane
        assert c.dist_indices["snap_dst"].get("aliases") == \
            {"snap_alias": {}}, c.dist_indices["snap_dst"]
        del node.indices["snap_src"].aliases["snap_alias"]
        via_alias = c.data.search("snap_alias",
                                  {"query": {"match": {"body": "gamma"}},
                                   "size": 30})
        assert via_alias["hits"]["total"] == 15
        assert via_alias["_shards"]["failed"] == 0
        # alias REMOVAL must propagate through the published metadata too
        # (a local-only delete would be resurrected by the next publish)
        node.update_aliases([{"remove": {"index": "snap_dst",
                                         "alias": "snap_alias"}}])
        assert c.dist_indices["snap_dst"]["aliases"] == {}
        from elasticsearch_tpu.utils.errors import IndexNotFoundException

        with pytest.raises(IndexNotFoundException):
            c.data.search("snap_alias", {"query": {"match_all": {}}})
        for i in ("0", "13", "29"):
            g = c.data.get_doc("snap_dst", i)
            assert g["found"] and g["_source"] == docs[i], g

        # restored scores match a single-process oracle restore
        oracle = Node(name="oracle")
        from elasticsearch_tpu.index.snapshots import restore_snapshot

        restore_snapshot(oracle, fs, "snap1")
        want = oracle.search("snap_src",
                             {"query": {"match": {"body": "gamma"}},
                              "size": 30})
        got_scores = {h["_id"]: h["_score"]
                      for h in got["hits"]["hits"]}
        want_scores = {h["_id"]: h["_score"]
                       for h in want["hits"]["hits"]}
        assert got_scores.keys() == want_scores.keys()
        for k, v in want_scores.items():
            assert got_scores[k] == pytest.approx(v, rel=1e-4)
        oracle.close()

        # a PARTIAL manifest (a shard's blobs missing) must refuse to
        # restore unless the caller opts in with partial=true — silently
        # restoring half an index as SUCCESS loses data invisibly
        from elasticsearch_tpu.index.snapshots import SnapshotException

        m["indices"]["snap_src"]["shards"][0] = {
            "blobs": [], "versions": {}, "failed": True}
        m["snapshot"] = "snap_partial"
        fs.put_manifest("snap_partial", m)
        with pytest.raises(SnapshotException, match="partial=true"):
            c.data._on_restore({
                "location": repo, "snapshot": "snap_partial",
                "rename_pattern": "snap_src",
                "rename_replacement": "snap_part"})
        assert "snap_part" not in c.dist_indices
        r = c.data.restore_snapshot(repo, "snap_partial",
                                    rename_pattern="snap_src",
                                    rename_replacement="snap_part",
                                    partial=True)
        # the missing shard is reported failed (it restored active but
        # EMPTY), matching the single-node path's accounting
        assert r["snapshot"]["shards"] == {"total": 2, "failed": 1,
                                           "successful": 1}, r
        got = c.data.search("snap_part",
                            {"query": {"match_all": {}}, "size": 0})
        # the failed shard restored EMPTY, the healthy one fully
        assert 0 < got["hits"]["total"] < 30, got["hits"]["total"]
        assert got["_shards"]["failed"] == 0, got["_shards"]
    finally:
        p.kill()
        p.wait()


def test_doc_level_and_scroll_ops_cross_host(master):
    """Doc-level REST ops (explain, termvectors) route to the doc's
    primary owner (the coordinator's local shards don't hold remote
    docs), and scroll on a distributed index pages through the FULL
    cluster-wide result set."""
    import json
    import urllib.request

    from elasticsearch_tpu.cluster.routing import shard_id_for
    from elasticsearch_tpu.rest.server import RestServer

    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    srv = RestServer(node, port=0)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        r = urllib.request.Request(
            base + path, method=method,
            data=json.dumps(body).encode() if body is not None else None)
        try:
            with urllib.request.urlopen(r) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        # number_of_replicas=1: every doc (and every .percolator
        # registration) lives on BOTH processes — the suggest freq and
        # percolate match assertions below prove the primary-owner
        # targeting + dedup (a naive broadcast would double everything)
        st, r = req("PUT", "/dlo", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        assert st == 200, r
        for i in range(30):
            req("PUT", f"/dlo/t/{i}", {"body": f"alpha beta tok{i}"})
        req("POST", "/dlo/_refresh")
        remote_id = next(
            str(i) for i in range(30)
            if c.data.owner_of("dlo", shard_id_for(str(i), 2))
            != c.local.node_id)

        # explain for a REMOTE doc: matched with a real score
        st, r = req("POST", f"/dlo/_explain/{remote_id}",
                    {"query": {"match": {"body": "alpha"}}})
        assert st == 200 and r["matched"], r
        assert r["explanation"]["value"] > 0, r

        # termvectors for a REMOTE doc: real terms with positions
        st, r = req("GET", f"/dlo/t/{remote_id}/_termvectors")
        assert st == 200, r
        terms = r["term_vectors"]["body"]["terms"]
        assert "alpha" in terms and "beta" in terms, sorted(terms)[:5]

        # scroll pages through ALL 30 docs cluster-wide
        st, r = req("POST", "/dlo/_search?scroll=1m",
                    {"query": {"match_all": {}}, "size": 12})
        assert st == 200 and r["hits"]["total"] == 30, r["hits"]["total"]
        sid = r["_scroll_id"]
        got = [h["_id"] for h in r["hits"]["hits"]]
        while True:
            st, r = req("POST", "/_search/scroll",
                        {"scroll": "1m", "scroll_id": sid})
            assert st == 200, r
            if not r["hits"]["hits"]:
                break
            got.extend(h["_id"] for h in r["hits"]["hits"])
        assert sorted(got, key=int) == [str(i) for i in range(30)], got

        # search_type=scan: first response carries NO hits by contract;
        # scroll pages deliver everything
        st, r = req("POST", "/dlo/_search?scroll=1m&search_type=scan",
                    {"query": {"match_all": {}}, "size": 12})
        assert st == 200 and r["hits"]["hits"] == [], r["hits"]
        assert r["hits"]["total"] == 30
        sid = r["_scroll_id"]
        got = []
        while True:
            st, r = req("POST", "/_search/scroll",
                        {"scroll": "1m", "scroll_id": sid})
            if not r["hits"]["hits"]:
                break
            got.extend(h["_id"] for h in r["hits"]["hits"])
        assert sorted(got, key=int) == [str(i) for i in range(30)], got

        # suggest merges across processes: 'alpha' is frequent on BOTH
        # owners' shards, so the merged freq must be the cluster total
        st, r = req("POST", "/dlo/_suggest", {
            "fix": {"text": "alpa", "term": {"field": "body"}}})
        assert st == 200, r
        opts = r["fix"][0]["options"]
        assert opts and opts[0]["text"] == "alpha", opts
        assert opts[0]["freq"] == 30, opts  # docs from BOTH processes
        assert r["_shards"]["failed"] == 0, r["_shards"]

        # root /_suggest (no index) also fans dist indices per owner
        st, r = req("POST", "/_suggest", {
            "fx": {"text": "alpa", "term": {"field": "body"}}})
        assert st == 200 and r["fx"][0]["options"][0]["freq"] == 30, r
        assert r["_shards"]["failed"] == 0, r["_shards"]

        # percolate: queries register as routed docs (disjoint subsets on
        # each owner); a match registered on the REMOTE owner must surface
        for qid, term, team in (("q_local", "alpha", "red"),
                                ("q2", "beta", "blue"),
                                ("q3", "zebra", "red")):
            st, _ = req("PUT", f"/dlo/.percolator/{qid}",
                        {"query": {"match": {"body": term}}, "team": team})
            assert st in (200, 201)
        req("POST", "/dlo/_refresh")
        st, r = req("POST", "/dlo/t/_percolate",
                    {"doc": {"body": "alpha beta words"}})
        assert st == 200, r
        assert r["total"] == 2, r
        assert {m["_id"] for m in r["matches"]} == {"q_local", "q2"}, r
        # aggs-under-percolate on a dist index: aggregates the MATCHED
        # registrations' metadata cluster-wide (the matched queries live
        # on different owners; partials reduce via the distributed
        # search, server.py::_dist_percolate). q3 (unmatched, team=red)
        # must not count.
        st, r = req("POST", "/dlo/t/_percolate", {
            "doc": {"body": "alpha beta words"},
            "aggs": {"teams": {"terms": {"field": "team"}}}})
        assert st == 200, (st, r)
        assert r["total"] == 2, r
        buckets = {b["key"]: b["doc_count"]
                   for b in r["aggregations"]["teams"]["buckets"]}
        assert buckets == {"red": 1, "blue": 1}, buckets
        # size truncates the match PAGE only: total and aggs still cover
        # all matches (owners fan without size; coordinator re-truncates)
        st, r = req("POST", "/dlo/t/_percolate", {
            "doc": {"body": "alpha beta words"}, "size": 1,
            "aggs": {"teams": {"terms": {"field": "team"}}}})
        assert st == 200 and r["total"] == 2 and len(r["matches"]) == 1, r
        buckets = {b["key"]: b["doc_count"]
                   for b in r["aggregations"]["teams"]["buckets"]}
        assert buckets == {"red": 1, "blue": 1}, buckets

        # field_stats merges across owners (doc_count must be the
        # cluster-wide 30, not a local subset or a replica-doubled 60)
        st, r = req("GET", "/dlo/_field_stats?fields=body&level=indices")
        assert st == 200, r
        fs = r["indices"]["dlo"]["fields"]["body"]
        assert fs["doc_count"] == 30, fs

        # more_like_this with a liked id resolves via the ROUTED get even
        # when the liked doc lives on the remote owner, and matches docs
        # cluster-wide (both shards)
        st, r = req("POST", "/dlo/_search", {
            "query": {"more_like_this": {
                "fields": ["body"], "like": [{"_id": remote_id}],
                "min_term_freq": 1, "min_doc_freq": 1}}, "size": 40})
        assert st == 200, r
        ids = {h["_id"] for h in r["hits"]["hits"]}
        assert remote_id not in ids  # liked doc excluded
        # every OTHER doc shares 'alpha beta' with the liked doc
        assert ids == {str(i) for i in range(30)} - {remote_id}, ids
    finally:
        srv.stop()
        p.kill()
        p.wait()


def test_snapshot_under_concurrent_writes(master, tmp_path):
    """Race safety (SURVEY §5): a distributed snapshot taken while client
    threads keep writing must neither crash (engine._locations mutating
    under iteration) nor produce an unreadable manifest — and restoring
    it yields a consistent prefix: every restored doc equals what was
    written, with no partial/corrupt blobs."""
    import threading

    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    repo = str(tmp_path / "racer")
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        c.data.create_index("race", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"n": {"type": "integer"}}}})
        for i in range(50):
            c.data.index_doc("race", str(i), {"n": i})
        c.data.refresh("race")

        stop = threading.Event()
        errors: list = []

        def writer(base):
            i = 0
            while not stop.is_set():
                try:
                    c.data.index_doc("race", f"w{base}-{i}", {"n": i})
                except Exception as e:  # pragma: no cover
                    errors.append(e)
                    return
                i += 1

        threads = [threading.Thread(target=writer, args=(t,), daemon=True)
                   for t in range(3)]
        for t in threads:
            t.start()
        try:
            r = c.data.create_snapshot(repo, "racy")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not errors, errors
        assert r["snapshot"]["shards"]["failed"] == 0, r

        res = c.data.restore_snapshot(repo, "racy",
                                      rename_pattern="race",
                                      rename_replacement="race2")
        assert res["snapshot"]["shards"]["failed"] == 0, res
        got = c.data.search("race2", {"query": {"match_all": {}},
                                      "size": 10_000})
        assert got["_shards"]["failed"] == 0
        ids = {h["_id"] for h in got["hits"]["hits"]}
        # the 50 pre-snapshot docs are all there; concurrent writes are
        # each either fully present or absent — and every present one
        # round-trips its source
        assert {str(i) for i in range(50)} <= ids, sorted(ids)[:60]
        for h in got["hits"]["hits"][:200]:
            assert set(h["_source"]) == {"n"}, h
    finally:
        p.kill()
        p.wait()


def test_three_process_replication_and_reheal(master):
    """World=3: replicas place on distinct nodes, a member's death
    promotes its primaries on survivors AND re-replicates back up to two
    copies per shard from the surviving copy (reconcile with multiple
    placement candidates — the 2-process tests can't exercise the
    candidate-selection order). Reference: RoutingNodes promotion +
    BalancedShardsAllocator."""
    node, c = master
    port = c.master_addr[1]
    p1 = _spawn_rank1(port)
    code2 = _member_code(port, rank=2, world=3, expect=3, name="rank2")
    p2 = subprocess.Popen([sys.executable, "-c", code2],
                          stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                          text=True)
    try:
        assert "JOINED" in p2.stdout.readline()
        assert _wait(lambda: len(node.cluster_state.nodes) == 3)
        c.data.create_index("tri", {
            "settings": {"number_of_shards": 3, "number_of_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "n": {"type": "integer"}}}})
        assig = c.dist_indices["tri"]["assignment"]
        # every shard: primary + replica on DISTINCT nodes; primaries
        # spread over all three processes
        assert all(len(set(o)) == 2 for o in assig.values()), assig
        assert {o[0] for o in assig.values()} == \
            set(node.cluster_state.nodes), assig
        for i in range(60):
            c.data.index_doc("tri", str(i), {"body": f"alpha tok{i}",
                                             "n": i})
        c.data.refresh("tri")
        r = c.data.search("tri", {"query": {"match_all": {}}, "size": 0})
        assert r["hits"]["total"] == 60

        p1.kill()  # hard death of one of three members
        p1.wait()
        assert _wait(lambda: len(node.cluster_state.nodes) == 2,
                     timeout=15.0)
        alive = set(node.cluster_state.nodes)
        # reconcile: every shard back to 2 copies on the two survivors
        # (recovery streams run async — poll)
        assert _wait(lambda: all(
            len(o) == 2 and set(o) <= alive
            for o in c.dist_indices["tri"]["assignment"].values()),
            timeout=25.0), c.dist_indices["tri"]["assignment"]
        r = c.data.search("tri", {"query": {"match_all": {}}, "size": 60})
        assert r["hits"]["total"] == 60, r["hits"]["total"]
        assert r["_shards"]["failed"] == 0, r["_shards"]
        assert {h["_id"] for h in r["hits"]["hits"]} == \
            {str(i) for i in range(60)}
    finally:
        p1.kill()
        p1.wait()
        p2.kill()
        p2.wait()


def test_delete_index_propagates_cluster_wide(master):
    """DELETE /{index} on a distributed index must drop it from the
    published metadata and remove every peer's local copy — a local-only
    delete would be resurrected by the next publish (and break the
    coordinator whose svc is gone while dist_indices still routes)."""
    from elasticsearch_tpu.cluster.search_action import ACTION_REST_PROXY

    node, c = master
    p = _spawn_rank1(c.master_addr[1])
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        c.data.create_index("delme", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"n": {"type": "integer"}}}})
        for i in range(10):
            c.data.index_doc("delme", str(i), {"n": i})
        c.data.refresh("delme")
        rank1 = next(nid for nid in node.cluster_state.nodes
                     if nid != c.local.node_id)
        node.delete_index("delme")
        assert "delme" not in c.dist_indices
        assert "delme" not in node.indices

        def _rank1_has():
            try:
                res = c.data._send(rank1, ACTION_REST_PROXY, {
                    "method": "GET", "path": "/delme", "params": {},
                    "body": ""})
            except Exception:
                return None
            return res["status"]

        # the peer removes its copy on the next publish
        assert _wait(lambda: _rank1_has() == 404, timeout=10.0), \
            _rank1_has()
        # re-creating the name works cleanly afterwards
        c.data.create_index("delme", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"n": {"type": "integer"}}}})
        c.data.index_doc("delme", "1", {"n": 1})
        c.data.refresh("delme")
        r = c.data.search("delme", {"query": {"match_all": {}}})
        assert r["hits"]["total"] == 1
    finally:
        p.kill()
        p.wait()


def test_percolator_registry_survives_recovery_stream(master):
    """A node that recovers a shard via the ops stream must also rebuild
    its in-memory percolator registry (the stream replays at engine
    level, bypassing the svc write path that maintains it) — otherwise a
    promoted copy serves percolates with an empty registry."""
    from elasticsearch_tpu.cluster.search_action import ACTION_REST_PROXY

    node, c = master
    # alone: register percolator queries (+ delete one so its tombstone
    # rides the stream too)
    c.data.create_index("pcr", {
        "settings": {"number_of_shards": 2, "number_of_replicas": 1},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    for qid, term in (("pq1", "hawk"), ("pq2", "owl"), ("dead", "crow")):
        c.data.index_doc("pcr", qid, {"query": {"match": {"body": term}}},
                         doc_type=".percolator")
    c.data.delete_doc("pcr", "dead")
    c.data.refresh("pcr")

    p = _spawn_rank1(c.master_addr[1])
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        assert _wait(lambda: all(
            len(o) == 2 for o in
            c.dist_indices["pcr"]["assignment"].values()), timeout=10.0)
        rank1 = next(nid for nid in node.cluster_state.nodes
                     if nid != c.local.node_id)
        import json as json_mod

        def _rank1_percolate():
            try:
                res = c.data._send(rank1, ACTION_REST_PROXY, {
                    "method": "POST", "path": "/pcr/t/_percolate",
                    "params": {},
                    "body": json_mod.dumps(
                        {"doc": {"body": "hawk and owl and crow"}})})
            except Exception:
                return None
            if res["status"] != 200:
                return None
            return sorted(m["_id"] for m in res["payload"]["matches"])

        # poll: the recovery stream runs async after the join; the NEW
        # node's own registry must match both live queries and NOT the
        # deleted one
        assert _wait(lambda: _rank1_percolate() == ["pq1", "pq2"],
                     timeout=20.0), _rank1_percolate()
    finally:
        p.kill()
        p.wait()


def test_master_restart_recovers_dist_metadata(tmp_path):
    """A master restart with a data path reloads the distributed-index
    metadata (the gateway-persisted cluster state): its own copies remap
    to the new node id, searches work again, and a rejoining member gets
    re-replicated via reconcile — without this, restart orphaned the
    layout while the shard data sat on disk."""
    dp = str(tmp_path / "master")
    node = Node(name="m1", data_path=dp)
    c = MultiHostCluster(node, rank=0, world=2, transport_port=_free_port(),
                         ping_interval=0, minimum_master_nodes=1)
    try:
        c.data.create_index("dur", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"n": {"type": "integer"}}}})
        for i in range(20):
            c.data.index_doc("dur", str(i), {"n": i})
        c.data.refresh("dur")
    finally:
        c.close()
        node.close()

    node2 = Node(name="m1b", data_path=dp)
    c2 = MultiHostCluster(node2, rank=0, world=2,
                          transport_port=_free_port(), ping_interval=0,
                          minimum_master_nodes=1)
    p = None
    try:
        assert "dur" in c2.dist_indices
        # the old id's copies remapped to the NEW local id
        assert all(o == [c2.local.node_id] for o in
                   c2.dist_indices["dur"]["assignment"].values()), \
            c2.dist_indices["dur"]["assignment"]
        r = c2.data.search("dur", {"query": {"match_all": {}},
                                   "size": 30})
        assert r["hits"]["total"] == 20, r["hits"]["total"]
        assert r["_shards"]["failed"] == 0, r["_shards"]
        # a joining member re-replicates from the restarted master
        p = _spawn_rank1(c2.master_addr[1])
        assert _wait(lambda: len(node2.cluster_state.nodes) == 2)
        assert _wait(lambda: all(
            len(o) == 2 for o in
            c2.dist_indices["dur"]["assignment"].values()), timeout=15.0)
    finally:
        if p is not None:
            p.kill()
            p.wait()
        c2.close()
        node2.close()


def test_lost_shard_resurrects_from_rejoining_member(master):
    """Gateway allocation: a shard whose ONLY copy lived on a member that
    died comes back when that member rejoins with its data_path — the
    master probes the joiner's on-disk shard and adopts it as primary
    (reference: GatewayAllocator primary allocation from shard stores).
    Until then the shard reads 'no active copies', a visible failure."""
    import tempfile

    from tests.integration.multihost_util import spawn_member

    node, c = master
    dp = tempfile.mkdtemp()
    port = c.master_addr[1]
    p = spawn_member(port, data_path=dp)
    try:
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        c.data.create_index("gw", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"n": {"type": "integer"}}}})
        assig = c.dist_indices["gw"]["assignment"]
        assert len({o[0] for o in assig.values()}) == 2, assig
        for i in range(30):
            c.data.index_doc("gw", str(i), {"n": i})
        c.data.refresh("gw")

        p.kill()  # the member's shard is now LOST (no replicas)
        p.wait()
        assert _wait(lambda: len(node.cluster_state.nodes) == 1,
                     timeout=15.0)
        lost = [sid for sid, o in
                c.dist_indices["gw"]["assignment"].items() if not o]
        assert len(lost) == 1, c.dist_indices["gw"]["assignment"]
        r = c.data.search("gw", {"query": {"match_all": {}}, "size": 40})
        assert r["_shards"]["failed"] == 1  # visible partial failure

        # the member restarts FROM ITS DATA PATH (new node id) and rejoins
        p = spawn_member(port, name="rank1b", data_path=dp)
        assert _wait(lambda: len(node.cluster_state.nodes) == 2)
        assert _wait(lambda: all(
            o for o in c.dist_indices["gw"]["assignment"].values()),
            timeout=20.0), c.dist_indices["gw"]["assignment"]
        r = c.data.search("gw", {"query": {"match_all": {}}, "size": 40})
        assert r["hits"]["total"] == 30, r["hits"]["total"]
        assert r["_shards"]["failed"] == 0, r["_shards"]
    finally:
        p.kill()
        p.wait()


def test_jax_distributed_initialize_smoke():
    """--coordinator path: jax.distributed.initialize with a 1-process world
    (in a subprocess — it must run before any JAX computation)."""
    port = _free_port()
    code = f"""
import sys
sys.path.insert(0, "/root/repo")
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from elasticsearch_tpu.cluster.bootstrap import initialize_distributed
initialize_distributed("127.0.0.1:{port}", 1, 0)
import jax
assert jax.process_index() == 0 and jax.process_count() == 1
print("DIST_OK", jax.device_count(), flush=True)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert "DIST_OK" in out.stdout, (out.stdout, out.stderr)


def test_nodes_stats_in_a_two_process_jax_world(tmp_path):
    """`--coordinator` with two real processes: ``jax.devices()`` lists
    the other rank's devices too, which are not addressable here (their
    ``memory_stats()`` raises). ``/_nodes/stats`` on every rank must
    answer, reporting that rank's own devices."""
    import json
    import os
    import urllib.request

    from tests.integration.multihost_util import REPO

    coord, tport = _free_port(), _free_port()
    rest = [_free_port(), _free_port()]
    env = dict(os.environ, JAX_PLATFORMS="cpu", ESTPU_WARMUP="0")
    env.pop("XLA_FLAGS", None)  # one local device per rank
    procs = [subprocess.Popen(
        [sys.executable, "-m", "elasticsearch_tpu.server",
         "--coordinator", f"127.0.0.1:{coord}", "--num-processes", "2",
         "--process-id", str(r), "--name", f"rank{r}",
         "--port", str(rest[r]), "--transport-port", str(tport),
         "--data-path", str(tmp_path / f"d{r}")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in (0, 1)]

    def stats(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/_nodes/stats", timeout=10) as r:
            return r.status, json.loads(r.read())

    def up(port):
        try:
            return stats(port)[0] == 200
        except OSError:
            return False

    try:
        assert _wait(lambda: up(rest[0]) and up(rest[1]), timeout=90.0), \
            [p.poll() for p in procs]
        for port in rest:
            st, body = stats(port)
            assert st == 200, body
            # rank 0 fans out to rank 1: both nodes' sections arrive whole
            assert body["nodes"], body
            for node in body["nodes"].values():
                acc = node["accelerator"]
                assert acc["platform"] == "cpu", acc
                assert acc["device_count"] == len(acc["devices"]) == 1, acc
        assert len(stats(rest[0])[1]["nodes"]) == 2
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            p.wait()
