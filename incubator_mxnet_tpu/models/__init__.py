"""Model families beyond the vision zoo.

- `bert`: Gluon-API BERT encoder (the reference ecosystem's GluonNLP
  BERT-base, BASELINE.json config 3) built on npx attention ops.
- `sharded_bert`: the same architecture as pure-jax functions with explicit
  dp/tp/sp shardings over a Mesh — the multi-chip flagship path.
- `gpt`: decoder-only causal LM (GluonNLP GPT-2 role) over the causal
  flash-attention path, with a sampling `generate` loop.
- `evabyte`: byte-level decoder (rotary, gated, bias-free) whose attention
  keeps one exact window and chunk summaries of everything before it; pure
  jax, served by `mx.serve` (`serve/eva.py`).
- `pangu`: openPangu-Ultra-MoE's block — latent attention (MLA), sandwich
  norms, a dropless sigmoid-routed expert layer that may hold a share of the
  experts; pure jax, served by `mx.serve` (`serve/mla.py`).
- `nemotron_h`: a decoder whose blocks differ in kind by a pattern string —
  Mamba-2 mixers that keep a fixed-size recurrent state, grouped-head
  attention in one block of eleven, latent expert layers with a biased
  sigmoid router over ungated ReLU^2 experts; pure jax, served by `mx.serve`
  (`serve/ssm.py`).
"""
from .bert import BERTClassifier, BERTEncoder, BERTModel, TransformerEncoderCell  # noqa: F401
from . import evabyte  # noqa: F401
from . import gpt  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import pangu  # noqa: F401
from . import sharded_bert  # noqa: F401
