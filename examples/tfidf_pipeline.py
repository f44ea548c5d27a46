"""The paper's TF-IDF application end-to-end (paper §3.2), plus its role in
this framework: flash-hash corpus statistics driving LM data filtering.

Run: PYTHONPATH=src python examples/tfidf_pipeline.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import TableGeometry
from repro.core.tfidf import TfIdfPipeline, tokenize
from repro.data import CorpusStats, LoaderConfig, SyntheticCorpus, make_batch

DOCS = [
    "flash devices have fast sequential writes and slow random writes",
    "hash tables rely on the randomness of the hash function",
    "the change segment buffers updates like a log structured file system",
    "counting hash tables keep a frequency per key and support deletion",
    "solid state drives wear out after too many erase write cycles",
] * 20

enable_compile_cache()
print("=== TF-IDF over the counting hash table (paper §3.2) ===")
# every table behind the pipeline is a FlashStore (DESIGN.md §8);
# backend="sim" | "device" | "sharded" swaps the engine with no other change
geom = TableGeometry(num_blocks=8, pages_per_block=16, entries_per_page=32)
pipe = TfIdfPipeline(geom, scheme="MDB-L", ram_buffer_pct=5.0, backend="sim")
for d in DOCS:
    pipe.add_document(tokenize(d))
pipe.finalize()
doc = tokenize(DOCS[0])
scores = pipe.tfidf(doc)
top = sorted(scores.items(), key=lambda kv: -kv[1])[:5]
print("top keywords of doc 0:", [t for t, _ in top])
print(f"'the' idf={pipe.idf('the'):.3f}  'sequential' idf="
      f"{pipe.idf('sequential'):.3f}")
s = pipe.term_table.stats()
print(f"I/O ledger: cleans={s['cleans']} block_ops={s['block_ops']} "
      f"page_ops={s['page_ops']}")

print("\n=== as the LM data layer (framework integration) ===")
corpus = SyntheticCorpus(num_docs=200, mean_doc_len=96, vocab_size=8000,
                         seed=7)
stats = CorpusStats.create(q_log2=15, r_log2=9)
for d in corpus:
    stats.ingest(d)
stats.flush()
scores = [stats.doc_score(corpus.doc_tokens(i)) for i in range(20)]
thr = float(np.median(scores))
lcfg = LoaderConfig(corpus=corpus, seq_len=128, global_batch=4,
                    microbatches=1, vocab_size=8000,
                    doc_filter=stats.doc_filter(thr))
batch = make_batch(lcfg, step=0)
print(f"filtered batch ready: tokens {batch['tokens'].shape}, "
      f"median doc score {thr:.3f}")
