"""Evaluation harness: bundled retrieval-QA dataset, the five BASELINE.json
pipeline configs, and a measured reference-architecture baseline.

The reference publishes no benchmark numbers (SURVEY.md §6), so parity and
the ≥10× latency target must be measured, not quoted. No public QA dataset
ships in this zero-egress image, so :mod:`dataset` synthesizes a
deterministic NQ-style retrieval-QA bundle (entity-rich facts + paraphrased
questions with gold document labels); :mod:`harness` runs pipeline configs
over it reporting recall@10 / p50 / QPS; :mod:`baseline` measures the
reference's as-shipped architecture — same pipeline shape, mock model
backends behind REAL loopback HTTP hops (its four process boundaries) —
as a conservative lower bound (zero network latency, zero model compute).
"""

from sentio_tpu.eval.dataset import EvalBundle, build_bundle
from sentio_tpu.eval.harness import EvalResult, recall_at_k, run_queries

__all__ = ["EvalBundle", "build_bundle", "EvalResult", "recall_at_k", "run_queries"]
