"""REST-spec tail endpoints (r4 sweep vs /root/reference/rest-api-spec/api):
shape tests for every spec file that previously had no route."""
import json
import urllib.request

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer


@pytest.fixture(scope="module")
def server():
    node = Node(name="spec-tail-node")
    srv = RestServer(node, host="127.0.0.1", port=0)
    srv.start(background=True)
    # a small corpus most tests share
    _req(srv, "PUT", "/lib", {"mappings": {"properties": {
        "title": {"type": "text"}, "tag": {"type": "keyword"},
        "year": {"type": "integer"}}}})
    for i, (t, tag, y) in enumerate([
            ("the quick brown fox", "a", 2001),
            ("lazy dogs sleep all day", "b", 2002),
            ("quick thinking wins races", "a", 2003)]):
        _req(srv, "PUT", f"/lib/_doc/{i}", {"title": t, "tag": tag, "year": y})
    _req(srv, "POST", "/lib/_refresh")
    yield srv
    srv.stop()
    node.close()


def _req(server, method, path, body=None, ndjson=None):
    url = f"http://127.0.0.1:{server.port}{path}"
    data = None
    if ndjson is not None:
        data = ndjson.encode()
    elif body is not None:
        data = json.dumps(body).encode()
    r = urllib.request.Request(url, data=data, method=method,
                               headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(r) as resp:
            payload = resp.read()
            try:
                return resp.status, json.loads(payload) if payload else None
            except json.JSONDecodeError:  # text endpoints (_cat, hot_threads)
                return resp.status, payload.decode()
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            return e.code, json.loads(payload) if payload else None
        except json.JSONDecodeError:
            return e.code, payload.decode()


def test_cluster_settings_roundtrip(server):
    st, body = _req(server, "PUT", "/_cluster/settings", {
        "persistent": {"indices.recovery.max_bytes_per_sec": "40mb"},
        "transient": {"cluster.routing.allocation.enable": "all"}})
    assert st == 200 and body["acknowledged"]
    st, body = _req(server, "GET", "/_cluster/settings")
    assert body["persistent"]["indices.recovery.max_bytes_per_sec"] == "40mb"
    # null deletes a key
    _req(server, "PUT", "/_cluster/settings",
         {"transient": {"cluster.routing.allocation.enable": None}})
    st, body = _req(server, "GET", "/_cluster/settings")
    assert "cluster.routing.allocation.enable" not in body["transient"]


def test_cluster_pending_tasks_and_reroute(server):
    st, body = _req(server, "GET", "/_cluster/pending_tasks")
    assert st == 200 and body["tasks"] == []
    st, body = _req(server, "POST", "/_cluster/reroute?explain=true", {
        "commands": [{"move": {"index": "lib", "shard": 0,
                               "from_node": "x", "to_node": "x"}}]})
    assert st == 200 and body["acknowledged"] and body["explanations"]
    st, body = _req(server, "POST", "/_cluster/reroute",
                    {"commands": [{"frobnicate": {}}]})
    assert st == 400


def test_hot_threads(server):
    # the test's own thread waits on the reply and burns no CPU: it is
    # listed only with the idle threads
    st, body = _req(server, "GET", "/_nodes/hot_threads?threads=1000&"
                    "snapshots=2&interval=10ms&ignore_idle_threads=false")
    assert st == 200 and ":::" in body and "MainThread" in body
    assert "cpu usage by thread 'MainThread'" in body


def test_global_count_field_stats_flush_optimize(server):
    st, body = _req(server, "GET", "/_count")
    assert st == 200 and body["count"] >= 3
    st, body = _req(server, "GET", "/_field_stats?level=indices")
    assert st == 200 and "year" in body["indices"]["lib"]["fields"]
    assert body["indices"]["lib"]["fields"]["year"]["min_value"] == 2001
    for path in ("/_flush", "/_optimize"):
        st, body = _req(server, "POST", path)
        assert st == 200 and body["_shards"]["failed"] == 0


def test_alias_single_ops_and_head_forms(server):
    st, body = _req(server, "PUT", "/lib/_alias/books")
    assert st == 200 and body["acknowledged"]
    st, _ = _req(server, "HEAD", "/_alias/books")
    assert st == 200
    st, _ = _req(server, "HEAD", "/lib/_alias/books")
    assert st == 200
    st, body = _req(server, "GET", "/lib/_alias")
    assert body["lib"]["aliases"].get("books") == {}
    st, body = _req(server, "GET", "/lib/_alias/bo*")
    assert "books" in body["lib"]["aliases"]
    st, body = _req(server, "DELETE", "/lib/_alias/books")
    assert st == 200
    st, _ = _req(server, "HEAD", "/_alias/books")
    assert st == 404


def test_template_and_type_exists(server):
    _req(server, "PUT", "/_template/spec_t",
         {"template": "spec-*", "settings": {}})
    st, _ = _req(server, "HEAD", "/_template/spec_t")
    assert st == 200
    st, _ = _req(server, "HEAD", "/_template/nope")
    assert st == 404
    st, _ = _req(server, "HEAD", "/lib/_mapping/_doc")
    assert st == 200
    st, _ = _req(server, "HEAD", "/lib/_mapping/ghosttype")
    assert st == 404


def test_get_field_mapping(server):
    st, body = _req(server, "GET", "/lib/_mapping/field/title")
    assert st == 200
    fm = body["lib"]["mappings"]["_doc"]["title"]
    assert fm["full_name"] == "title"
    assert fm["mapping"]["title"]["type"] == "text"
    st, body = _req(server, "GET", "/_mapping/field/t*")
    assert {"title", "tag"} <= set(body["lib"]["mappings"]["_doc"])


def test_segments_and_recovery_json(server):
    st, body = _req(server, "GET", "/lib/_segments")
    assert st == 200
    shards = body["indices"]["lib"]["shards"]
    segs = shards["0"][0]["segments"]
    assert all(v["num_docs"] >= 0 for v in segs.values())
    st, body = _req(server, "GET", "/lib/_recovery")
    assert body["lib"]["shards"][0]["stage"] in ("DONE", "INIT")
    st, body = _req(server, "GET", "/_recovery")
    assert "lib" in body


def test_upgrade_and_clear_cache(server):
    st, body = _req(server, "POST", "/lib/_upgrade")
    assert st == 200 and "lib" in body["upgraded_indices"]
    st, body = _req(server, "GET", "/lib/_upgrade")
    assert body["indices"]["lib"]["size_to_upgrade_in_bytes"] == 0
    st, body = _req(server, "POST", "/lib/_cache/clear")
    assert st == 200 and body["_shards"]["failed"] == 0
    # the index still searches after a cache clear
    st, body = _req(server, "POST", "/lib/_search",
                    {"query": {"match": {"title": "quick"}}})
    assert body["hits"]["total"] == 2


def test_percolate_count_and_mpercolate(server):
    _req(server, "PUT", "/pq", {"mappings": {"properties": {
        "msg": {"type": "text"}}}})
    _req(server, "PUT", "/pq/.percolator/1",
         {"query": {"match": {"msg": "alert"}}})
    _req(server, "POST", "/pq/_refresh")
    st, body = _req(server, "POST", "/pq/_doc/_percolate/count"
                    .replace("_doc/", "doc/"),
                    {"doc": {"msg": "red alert now"}})
    assert st == 200 and body["total"] == 1
    nd = "\n".join([
        json.dumps({"percolate": {"index": "pq", "type": "doc"}}),
        json.dumps({"doc": {"msg": "alert two"}}),
        json.dumps({"percolate": {"index": "missing-idx", "type": "doc"}}),
        json.dumps({"doc": {"msg": "x"}}),
    ]) + "\n"
    st, body = _req(server, "POST", "/_mpercolate", ndjson=nd)
    assert st == 200
    assert body["responses"][0]["total"] == 1
    assert body["responses"][1]["status"] == 404


def test_mtermvectors(server):
    st, body = _req(server, "POST", "/_mtermvectors", {
        "docs": [{"_index": "lib", "_id": "0", "fields": ["title"]},
                 {"_index": "lib", "_id": "404"}]})
    assert st == 200
    d0 = body["docs"][0]
    assert "quick" in d0["term_vectors"]["title"]["terms"]
    st, body = _req(server, "GET", "/lib/_mtermvectors", {"ids": ["1", "2"]})
    assert len(body["docs"]) == 2
    assert "lazy" in body["docs"][0]["term_vectors"]["title"]["terms"]


def test_mlt_endpoint(server):
    st, body = _req(server, "GET",
                    "/lib/doc/0/_mlt?min_term_freq=1&min_doc_freq=1")
    assert st == 200
    ids = [h["_id"] for h in body["hits"]["hits"]]
    assert "2" in ids  # shares "quick" with doc 0


def test_search_exists_and_search_shards(server):
    st, body = _req(server, "POST", "/lib/_search/exists",
                    {"query": {"term": {"tag": "a"}}})
    assert st == 200 and body["exists"] is True
    st, body = _req(server, "POST", "/lib/_search/exists",
                    {"query": {"term": {"tag": "zzz"}}})
    assert st == 404 and body["exists"] is False
    st, body = _req(server, "GET", "/lib/_search_shards")
    assert st == 200
    assert body["shards"][0][0]["index"] == "lib"
    assert list(body["nodes"])  # node entry present


def test_snapshot_status_and_verify(server, tmp_path_factory):
    loc = str(tmp_path_factory.mktemp("repo"))
    _req(server, "PUT", "/_snapshot/specrepo",
         {"type": "fs", "settings": {"location": loc}})
    st, body = _req(server, "POST", "/_snapshot/specrepo/_verify")
    assert st == 200 and list(body["nodes"])
    _req(server, "PUT", "/_snapshot/specrepo/s1",
         {"indices": "lib", "wait_for_completion": True})
    st, body = _req(server, "GET", "/_snapshot/specrepo/s1/_status")
    assert st == 200
    snap = body["snapshots"][0]
    assert snap["state"] == "SUCCESS" and snap["shards_stats"]["failed"] == 0
    st, body = _req(server, "GET", "/_snapshot/_status")
    assert body["snapshots"] == []


def test_indexed_scripts_and_script_query(server):
    st, body = _req(server, "PUT", "/_scripts/painless/year_gate",
                    {"script": "doc['year'].value > params.y"})
    assert st == 201
    st, body = _req(server, "GET", "/_scripts/painless/year_gate")
    assert body["found"] and "doc['year']" in body["script"]
    # a stored script is usable from a query spec by id
    st, body = _req(server, "POST", "/lib/_search", {"query": {
        "script": {"script": {"id": "year_gate", "params": {"y": 2001}}}}})
    assert body["hits"]["total"] == 2
    st, body = _req(server, "DELETE", "/_scripts/painless/year_gate")
    assert st == 200
    st, body = _req(server, "GET", "/_scripts/painless/year_gate")
    assert st == 404
    # invalid scripts are rejected at PUT time
    st, body = _req(server, "PUT", "/_scripts/painless/evil",
                    {"script": "__import__('os')"})
    assert st >= 400


def test_cat_help_and_get_scroll(server):
    st, body = _req(server, "GET", "/_cat")
    assert st == 200 and "/_cat/indices" in body
    st, body = _req(server, "POST", "/lib/_search?scroll=1m",
                    {"query": {"match_all": {}}, "size": 1})
    sid = body["_scroll_id"]
    st, body = _req(server, "GET", f"/_search/scroll?scroll_id={sid}")
    assert st == 200 and len(body["hits"]["hits"]) == 1


def test_typed_routes(server):
    """ES 2.0 typed forms: /{index}/{type}[/{id}] CRUD + sub-resources."""
    st, body = _req(server, "POST", "/lib/book",
                    {"title": "typed auto id", "tag": "c", "year": 2004})
    assert st == 201 and body["created"]
    auto_id = body["_id"]
    st, _ = _req(server, "POST", "/lib/_refresh")
    st, body = _req(server, "HEAD", f"/lib/book/{auto_id}")
    assert st == 200
    st, body = _req(server, "HEAD", "/lib/book")
    assert st == 200  # type with live docs
    st, body = _req(server, "HEAD", "/lib/nosuchtype")
    assert st == 404
    st, body = _req(server, "GET", f"/lib/book/{auto_id}/_source")
    assert st == 200 and body["title"] == "typed auto id"
    st, body = _req(server, "POST", f"/lib/book/{auto_id}/_update",
                    {"doc": {"year": 2005}})
    assert st == 200
    _req(server, "POST", "/lib/_refresh")  # _explain searches segments
    st, body = _req(server, "GET", f"/lib/book/{auto_id}/_explain",
                    {"query": {"match": {"title": "typed"}}})
    assert st == 200
    st, body = _req(server, "DELETE", f"/lib/book/{auto_id}")
    assert st == 200
    _req(server, "POST", "/lib/_refresh")
    # an unclaimed /_x segment must NOT bind as a type
    st, body = _req(server, "POST", "/lib/_nosuch", {"title": "x"})
    assert st == 400


def test_root_scoped_forms(server):
    st, body = _req(server, "GET", "/_mapping")
    assert st == 200 and "lib" in body and "mappings" in body["lib"]
    st, body = _req(server, "GET", "/_settings")
    assert st == 200 and "lib" in body
    st, body = _req(server, "GET", "/_settings/index.number_of_shards")
    assert st == 200
    assert list(body["lib"]["settings"]["index"]) == ["number_of_shards"]
    st, body = _req(server, "GET", "/_alias")
    assert st == 200 and "lib" in body
    st, body = _req(server, "GET", "/_template")
    assert st == 200
    st, body = _req(server, "GET", "/_refresh")
    assert st == 200 and body["_shards"]["failed"] == 0
    st, body = _req(server, "GET", "/_warmer")
    assert st == 200


def test_index_feature_form(server):
    """GET /{index}/{feature} (indices.get): comma list of features."""
    st, body = _req(server, "GET", "/lib/_settings,_mappings")
    assert st == 200
    assert set(body["lib"]) == {"settings", "mappings"}
    st, body = _req(server, "GET", "/lib/_aliases")
    assert st == 200
    st, body = _req(server, "GET", "/lib/bogusfeature")
    assert st == 400


def test_scoped_cat_and_cluster_forms(server):
    st, body = _req(server, "GET", "/_cat/indices/lib?format=json")
    assert st == 200 and len(body) == 1 and body[0]["index"] == "lib"
    st, body = _req(server, "GET", "/_cat/indices/nomatch*?format=json")
    assert st == 200 and body == []
    st, body = _req(server, "GET", "/_cat/shards/lib?format=json")
    assert st == 200 and all(r["index"] == "lib" for r in body)
    st, body = _req(server, "GET", "/_cluster/health/lib")
    assert st == 200 and "status" in body
    st, body = _req(server, "GET", "/_cluster/state/metadata")
    assert st == 200
    st, body = _req(server, "GET", "/_nodes/stats/indices")
    assert st == 200


def test_scroll_path_form_and_clear(server):
    st, body = _req(server, "POST", "/lib/_search?scroll=1m",
                    {"query": {"match_all": {}}, "size": 1})
    sid = body["_scroll_id"]
    st, body = _req(server, "GET", f"/_search/scroll/{sid}")
    assert st == 200 and len(body["hits"]["hits"]) == 1
    st, body = _req(server, "DELETE", f"/_search/scroll/{sid}")
    assert st == 200 and body["num_freed"] == 1


def test_root_warmer_and_mapping_type_forms(server):
    st, body = _req(server, "PUT", "/_warmer/w_all",
                    {"query": {"match_all": {}}})
    assert st == 200
    st, body = _req(server, "GET", "/_warmer/w_all")
    assert st == 200 and body["lib"]["warmers"]["w_all"]
    st, body = _req(server, "GET", "/lib/book/_warmer/w_all")
    assert st == 200
    st, body = _req(server, "DELETE", "/lib/_warmer/w_all")
    assert st == 200
    # root put_mapping applies to every index
    st, body = _req(server, "PUT", "/_mapping/doc",
                    {"properties": {"extra_root": {"type": "keyword"}}})
    assert st == 200 and body["acknowledged"]
    st, body = _req(server, "GET", "/_mapping/doc")
    assert "extra_root" in json.dumps(body)


def test_unindexed_search_template(server):
    st, body = _req(server, "POST", "/_search/template", {
        "inline": {"query": {"term": {"tag": "{{t}}"}}},
        "params": {"t": "b"}})
    assert st == 200 and body["hits"]["total"] == 1
