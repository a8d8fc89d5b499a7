"""Model zoo for the target configs (BASELINE.json): MNIST LeNet, ResNet-50,
BERT-base pretraining, Transformer NMT, DeepFM CTR.

Two styles:
- program-mode models built with the fluid-parity layers API (paddle_tpu.layers)
  — the reference book-test style (tests/book/*, SURVEY.md §4);
- functional SPMD models (bert.py, resnet.py) — init/apply over param pytrees,
  designed for the parallel/ engine and the performance benchmarks; and the
  causal decoders, each a ``TransformerConfig`` and a label over
  ``parallel/decoder.py``'s one trainer (olmoe.py, smallthinker.py, lfm2.py,
  brumby.py, mistral4.py, trinity.py, jamba.py, nemotron_h.py, ouro.py,
  kimi_linear.py, keye_vl2.py, dots3.py, solar_open2.py, sdar.py,
  kanana2.py).
"""

from . import bert  # noqa: F401
from . import book  # noqa: F401  (word2vec, recommender, sentiment, SRL-CRF)
