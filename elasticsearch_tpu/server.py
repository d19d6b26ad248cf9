"""Launcher: ``python -m elasticsearch_tpu.server`` ≈ ``bin/elasticsearch``.

Reference: org/elasticsearch/bootstrap/Bootstrap.java + bin/elasticsearch.
"""
from __future__ import annotations

import argparse
import signal
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(prog="elasticsearch_tpu")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--name", default="node-1")
    ap.add_argument("--cluster-name", default="elasticsearch_tpu")
    ap.add_argument("--data-path", default=None, help="directory for translog durability")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the jax.distributed coordinator "
                         "(process 0); enables the multi-host control plane "
                         "with rank-0 master over the TCP transport")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--transport-port", type=int, default=9300,
                    help="TCP control-plane port (rank 0 binds it; other "
                         "ranks dial the coordinator host on it)")
    ap.add_argument("--minimum-master-nodes", type=int, default=None,
                    help="election/publish quorum; default: majority of "
                         "the master-eligible voting configuration")
    args = ap.parse_args(argv)

    from elasticsearch_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()  # persistent XLA cache: warm-start restarts

    cluster = None
    if args.coordinator:
        from elasticsearch_tpu.cluster.bootstrap import initialize_distributed

        initialize_distributed(args.coordinator, args.num_processes,
                               args.process_id)

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.server import RestServer

    node = Node(name=args.name, data_path=args.data_path, cluster_name=args.cluster_name)
    if args.coordinator:
        from elasticsearch_tpu.cluster.bootstrap import MultiHostCluster

        cluster = MultiHostCluster(
            node, args.process_id, args.num_processes,
            bind_host=args.host, transport_port=args.transport_port,
            master_host=args.coordinator.split(":")[0],
            minimum_master_nodes=args.minimum_master_nodes)
        role = "master" if cluster.is_master else "data"
        print(f"[{args.name}] joined cluster as {role} "
              f"(rank {args.process_id}/{args.num_processes})", flush=True)
    server = RestServer(node, host=args.host, port=args.port)
    print(f"[{args.name}] listening on http://{server.host}:{server.port}", flush=True)

    def _stop(*_):
        print("shutting down", flush=True)
        if cluster is not None:
            cluster.close()
        # close the node IN the handler, stop the listener from a helper
        # thread: this handler interrupted serve_forever on THIS thread,
        # so a same-thread httpd.shutdown() waits forever for the loop it
        # suspended — the old sequence deadlocked here and node.close()
        # (translog flush, program-census persistence) never ran
        import threading

        threading.Thread(target=server.stop, daemon=True).start()
        node.close()
        sys.exit(0)  # unwinds serve_forever; the stopper thread's
        # server_close then runs against an already-exited loop

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _stop)
    server.start(background=False)


if __name__ == "__main__":
    main()
