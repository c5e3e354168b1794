"""The deployment a cell serves: collection, index and cascade.

A configuration fixes its collection (sizes, term law and the seed it is
drawn from) and its cascade's training log, as a search deployment fixes
the collection it indexes and the log its predictor learnt from; ``--seed``
draws only the traffic.  The program builds the index
(``retrieval.index.build_index``): that is most of a cold set-up, and
only the program can make it shorter.  The cascade is labelled with the
plain reference's lists and fitted here (``harness.cascade``), so neither
the server nor the reference reads tables the program trained.

Each is kept in ``bench/.cache``, one entry each, like the prebuilt index
a deployment loads.  An entry's key holds the configuration's sizes and a
hash of the source files that make it, and no others: the collection and
index under the program's index, scoring and corpus modules; the
reference's collection-wide quantities under the reference; the cascade
under the reference, the labelling and the forest.  A change to a kernel
or to the serving path therefore still finds the deployment built.  A run
that misses rebuilds and replaces the entry.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from pathlib import Path

import numpy as np

from harness import cascade as cascade_lib
from harness import data, store

__all__ = ["Deployment", "load_deployment", "collection_key",
           "cascade_key", "source_hash"]

SRC = Path(__file__).resolve().parents[2] / "src"
HERE = Path(__file__).resolve().parent

#: the program's modules that build the index, and this package's that
#: make and store the collection
INDEX_SOURCES = ("repro/retrieval/index.py", "repro/retrieval/scoring.py",
                 "repro/retrieval/corpus.py")
HARNESS_SOURCES = ("data.py", "build.py", "store.py")
#: this package's modules that label and fit the cascade
CASCADE_SOURCES = ("cascade.py", "check.py")


@dataclasses.dataclass
class Deployment:
    config: dict
    collection: data.Collection
    freq: np.ndarray            # (vocab,) term frequency in the collection
    index: object               # the program's InvertedIndex
    glob: dict                  # the reference's collection-wide quantities
    train_terms: np.ndarray     # (n, max_len) the cascade's training log
    forest: list                # per cutoff node, the forest's tables
    cutoffs: tuple
    hits: dict                  # entry name -> whether the cache held it
    seconds: dict               # phase -> seconds


def source_hash(paths) -> str:
    """Digest of the named source files."""
    h = hashlib.sha256()
    for p in paths:
        p = Path(p)
        h.update(p.name.encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def _key(*parts) -> str:
    return hashlib.sha256(json.dumps(parts, sort_keys=True).encode()
                          ).hexdigest()


def collection_key(cfg: dict, src: Path = SRC) -> str:
    """The collection entry's key: its sizes and the sources that make
    it."""
    return _key("collection", cfg["collection"], source_hash(
        [src / f for f in INDEX_SOURCES]
        + [HERE / f for f in HARNESS_SOURCES]))


def cascade_key(cfg: dict, ckey: str, ref_file) -> str:
    """The cascade entry's key: the collection's, the training log, the
    query law, the serving sizes and the sources that label and fit it."""
    return _key("cascade", ckey, cfg["training_log"], cfg["query_law"],
                cfg["serving"], source_hash(
                    [ref_file] + [HERE / f for f in CASCADE_SOURCES]))


def _build_index(cfg: dict):
    from repro.retrieval import corpus as corpus_lib
    from repro.retrieval import index as index_lib

    c = cfg["collection"]
    col = data.make_collection(c["n_docs"], c["vocab"], c["mean_doc_len"],
                               c["sigma_doc_len"], c["zipf_s"], c["seed"])
    corpus = corpus_lib.Corpus(
        config=corpus_lib.CorpusConfig(
            n_docs=c["n_docs"], vocab=c["vocab"],
            mean_doc_len=c["mean_doc_len"],
            sigma_doc_len=c["sigma_doc_len"], zipf_s=c["zipf_s"],
            seed=c["seed"]),
        doc_ids=col.doc_ids, term_ids=col.term_ids, counts=col.counts,
        doc_len=col.doc_len)
    return index_lib.build_index(corpus)


def _entry(where: Path, key: str, make, hits: dict, name: str):
    obj = store.load(where, key)
    hits[name] = obj is not None
    if obj is None:
        obj = make()
        store.save(obj, where, key)
    return obj


def load_deployment(cfg: dict, cache: Path, ref_mod,
                    log=print) -> Deployment:
    """The configuration's deployment, from ``cache`` or built anew;
    ``ref_mod`` is the configuration's reference module."""
    hits, seconds = {}, {}
    t0 = time.perf_counter()
    ckey = collection_key(cfg)
    index = _entry(cache / "collection", ckey, lambda: _build_index(cfg),
                   hits, "collection")
    c = index.corpus        # the collection as this package made it
    col = data.Collection(n_docs=int(c.config.n_docs),
                          vocab=int(c.config.vocab), doc_ids=c.doc_ids,
                          term_ids=c.term_ids, counts=c.counts,
                          doc_len=c.doc_len)
    seconds["collection"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref_src = source_hash([ref_mod.__file__])
    glob = _entry(cache / f"reference-{Path(ref_mod.__file__).stem}",
                  _key("reference", ckey, ref_src),
                  lambda: ref_mod.prepare(col), hits, "reference")
    seconds["reference"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    freq = data.term_freq(col)
    t, s = cfg["training_log"], cfg["serving"]
    train_terms = data.make_queries(
        freq, t["n_queries"], np.random.default_rng(t["seed"]),
        **cfg["query_law"])

    def fit():
        ref = ref_mod.Reference(col, s, glob, train_terms)
        classes = cascade_lib.label(ref, train_terms, s["cutoffs"],
                                    rbp_p=t["rbp_p"], tau=t["tau"])
        return cascade_lib.fit_cascade(
            ref.features(train_terms), classes, len(s["cutoffs"]),
            **t["forest"])

    kkey = cascade_key(cfg, ckey, ref_mod.__file__)
    forest = _entry(cache / f"cascade-{cfg['name']}", kkey, fit, hits,
                    "cascade")
    seconds["cascade"] = time.perf_counter() - t0
    log(f"deployment {cfg['name']}: cache hits {hits}, seconds "
        f"{ {k: round(v, 1) for k, v in seconds.items()} }")
    return Deployment(config=cfg, collection=col, freq=freq, index=index,
                      glob=glob, train_terms=train_terms, forest=forest,
                      cutoffs=tuple(s["cutoffs"]), hits=hits,
                      seconds=seconds)
