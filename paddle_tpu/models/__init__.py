"""Model zoo built on the layers DSL — the book/models configs of the
reference (python/paddle/fluid/tests/book/, BASELINE.json configs):
MNIST MLP, ResNet image classification, Transformer/BERT, word2vec, DeepFM.

Each builder appends to the current default main/startup programs (use
`program_guard` for isolation) and returns the named output Variables.
"""
from . import decoder_moe  # noqa: F401
from . import deepfm  # noqa: F401
from . import mlp  # noqa: F401
from . import resnet  # noqa: F401
from . import seq2seq  # noqa: F401
from . import transformer  # noqa: F401
from . import word2vec  # noqa: F401
