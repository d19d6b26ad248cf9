"""Where does a score gap come from? Loads a text configuration as a run
does, sends hand-picked `match` queries (dense-row terms only, tail terms
only, mixed, pool members) singly and as one `_msearch` through the
server's dispatch, and prints each hit's gap to the plain reference beside
the kernels that served. A diagnostic for the builder, not a measurement.

    python3 benchmarks/tools/score_probe.py --config msmarco-passage-shard --seed 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="msmarco-passage-shard")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from benchmarks import contract, loaders
    from benchmarks.metrics import counters
    from elasticsearch_tpu.rest.server import RestController

    import jax

    table = contract.load_table()
    cfg_entry = contract.config_of(table, args.config)
    with open(os.path.join(contract.ROOT, cfg_entry["file"])) as fh:
        cfg = json.load(fh)
    loaded = loaders.load(cfg, args.seed, jax.devices()[:1], args.rehearse)
    ctl = RestController(loaded.node)
    ref = loaded.reference
    c = loaded.shards[0]
    print("device", jax.devices()[0].device_kind, "df[0,3,63,64,200,5000]",
          [int(c.df[t]) for t in (0, 3, 63, 64, 200, min(5000, c.vocab - 1))])

    def kernels():
        snap = counters.parse(loaded.node.metrics.expose())
        return dict(snap.get("estpu_kernel_dispatch_total", {}))

    def gaps(terms, hits):
        sc = ref.scores(np.asarray(terms))
        out = []
        for h in hits:
            want = sc[int(h["_id"])]
            out.append((h["_score"] - want) / want if want else float("nan"))
        order = np.lexsort((np.arange(sc.size), -sc))[:len(hits)]
        same = [int(h["_id"]) for h in hits] == order.tolist()
        return out, same

    def body(terms):
        return {"query": {"match": {loaded.field: " ".join(
            f"t{t}" for t in terms)}}, "size": 10, "_source": False}

    last = min(5000, c.vocab - 1)
    probes = [[3], [40], [last], [200], [3, 40], [last, 200], [3, last],
              [0, 1, 2, 3], [3, 40, last, 200, 900]]
    probes += [loaded.pool[i].tolist() for i in range(8)]
    for rnd in (1, 2):
        for terms in probes:
            k0 = kernels()
            st, resp = ctl.dispatch(
                "POST", f"/{loaded.index}/_search", {},
                json.dumps(body(terms)).encode(), headers={})
            k1 = kernels()
            used = {k.split('"')[1]: int(v - k0.get(k, 0)) for k, v in
                    k1.items() if v - k0.get(k, 0)}
            g, same = gaps(terms, resp["hits"]["hits"])
            print(f"single r{rnd} terms={terms} status={st} ids_equal={same}"
                  f" max_gap={max(map(abs, g)):.3e} gaps="
                  f"{[f'{x:+.1e}' for x in g[:4]]} kernels={used}")
    lines = []
    for terms in probes:
        lines.append(json.dumps({"index": loaded.index}))
        lines.append(json.dumps(body(terms)))
    k0 = kernels()
    st, resp = ctl.dispatch("POST", "/_msearch", {},
                            ("\n".join(lines) + "\n").encode(), headers={})
    k1 = kernels()
    print("msearch kernels", {k.split('"')[1]: int(v - k0.get(k, 0))
                              for k, v in k1.items() if v - k0.get(k, 0)})
    for terms, r in zip(probes, resp["responses"]):
        g, same = gaps(terms, r["hits"]["hits"])
        print(f"msearch terms={terms} ids_equal={same} "
              f"max_gap={max(map(abs, g)):.3e}")
    loaded.node.close()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
