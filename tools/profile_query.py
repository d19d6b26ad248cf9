"""Profile the single-query product path on the TPU (bench headline)."""
import cProfile
import io
import pstats
import sys
import time

import numpy as np

docs = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 18
sys.argv = [sys.argv[0]]  # keep bench's module-level argparse inert
sys.path.insert(0, "/root/repo")
import bench

from elasticsearch_tpu.utils.platform import enable_compilation_cache

enable_compilation_cache()

vocab = 30000
u_doc, tf, tfn, offsets, df, idf, doc_len = bench.build_corpus(docs, vocab, 42)
node, seg = bench.make_msmarco_node(u_doc, tf, tfn, offsets, df, doc_len,
                                    docs, vocab)
seg.inverted["body"].dense_block()
qs = bench.make_queries(8, vocab, df, 42)
bodies = [{"query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
           "size": 10} for q in qs]
for b in bodies:
    node.search("msmarco", b)
# steady state timing
times = []
for _ in range(3):
    for b in bodies:
        t0 = time.perf_counter()
        node.search("msmarco", b)
        times.append(time.perf_counter() - t0)
print(f"docs={docs} p50={np.percentile(np.array(times)*1000, 50):.2f} ms",
      file=sys.stderr)

pr = cProfile.Profile()
pr.enable()
for _ in range(3):
    for b in bodies:
        node.search("msmarco", b)
pr.disable()
s = io.StringIO()
pstats.Stats(pr, stream=s).sort_stats("cumulative").print_stats(45)
print(s.getvalue(), file=sys.stderr)
