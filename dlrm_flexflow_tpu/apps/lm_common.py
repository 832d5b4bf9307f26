"""What the language-model apps share (``mla_moe_lm``, ``gdn_moe_lm``,
``kda_moe_lm``): the token loss, the embedding's initial scale and the
optimizer.  Each app re-exports them under its own name, which is what
the benchmark's families and the tests import.
"""

from __future__ import annotations

import jax

from ..losses import sparse_categorical_crossentropy_from_logits
from ..optim import AdamOptimizer

#: the embedding is drawn at torch.nn.Embedding's default scale, not at
#: ``initializer_range``: at the matrices' 0.02 the attention branches'
#: mean over positions, one vector at every position of a freshly
#: initialised model, outweighs the token's own vector (8-29x after two
#: layers) and every token selects the same experts; at 1.0 the token's
#: own vector leads the residual stream, as it does in a trained model
EMBEDDING_STDDEV = 1.0


def token_loss(logits, labels):
    """Mean over positions of the cross-entropy of (B, S, V) logits with
    (B, S, 1) token labels, in f32; timed with the head it follows."""
    with jax.named_scope("ff.lm.head"):
        return sparse_categorical_crossentropy_from_logits(logits, labels)


token_loss.__name__ = "sparse_token_crossentropy"  # compile: sparse labels


def optimizer(cfg) -> AdamOptimizer:
    """Dense Adam on every tensor, the embedding included, from a
    config's ``learning_rate`` and ``adam_*``."""
    return AdamOptimizer(lr=cfg.learning_rate, beta1=cfg.adam_beta1,
                         beta2=cfg.adam_beta2, epsilon=cfg.adam_epsilon)
