"""ERNIE encoder family (BASELINE.json config 5 names ERNIE-3.0).

ERNIE's architecture is the BERT post-LN encoder plus a task-type embedding
stream (multi-task pretraining); its signature knowledge-masking lives in the
DATA pipeline (entity/phrase spans), so the model side adds exactly the
task-embedding and the heads. Reference surface: ERNIE models live in
PaddleNLP built on python/paddle/nn (transformer.py) — here they are
first-class, reusing the paddle_tpu BERT blocks.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from .bert import BertEmbeddings, BertLayer


@dataclass
class ErnieConfig:
    vocab_size: int = 18000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0          # 0 -> 4*hidden
    max_position_embeddings: int = 512
    type_vocab_size: int = 4            # sentence types (a/b + padding kinds)
    task_type_vocab_size: int = 16      # ERNIE's task-id embedding stream
    use_task_id: bool = True
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-12
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size


def ernie_tiny(**overrides) -> "ErnieConfig":
    cfg = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
               max_position_embeddings=128)
    cfg.update(overrides)
    return ErnieConfig(**cfg)


class ErnieEmbeddings(nn.Layer):
    """BERT embeddings + the task-type stream (the ERNIE delta)."""

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.base = BertEmbeddings(config)
        self.task_type_embeddings = (
            nn.Embedding(config.task_type_vocab_size, config.hidden_size)
            if config.use_task_id else None)

    def forward(self, input_ids, token_type_ids=None, task_type_ids=None):
        if self.task_type_embeddings is None or task_type_ids is None:
            return self.base(input_ids, token_type_ids)
        # inject the task embedding before the shared LayerNorm/dropout:
        # recompute the sum the way BertEmbeddings does, plus the task term
        from .. import ops
        b, s = input_ids.shape
        pos = ops.arange(0, s, dtype="int32").unsqueeze(0)
        emb = (self.base.word_embeddings(input_ids)
               + self.base.position_embeddings(pos)
               + self.task_type_embeddings(task_type_ids))
        if token_type_ids is not None:
            emb = emb + self.base.token_type_embeddings(token_type_ids)
        return self.base.dropout(self.base.layer_norm(emb))


class ErnieModel(nn.Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        self.embeddings = ErnieEmbeddings(config)
        self.encoder = nn.LayerList([BertLayer(config)
                                     for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)
        normal = nn.initializer.Normal(mean=0.0, std=config.initializer_range)
        for _, p in self.named_parameters():
            if p.ndim >= 2:
                p.set_value(normal(tuple(p.shape), p.dtype))

    def forward(self, input_ids, token_type_ids=None, task_type_ids=None,
                attn_mask=None):
        x = self.embeddings(input_ids, token_type_ids, task_type_ids)
        for layer in self.encoder:
            x = layer(x, attn_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForSequenceClassification(nn.Layer):
    def __init__(self, config: ErnieConfig, num_classes: int = 2):
        super().__init__()
        self.ernie = ErnieModel(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, task_type_ids=None,
                attn_mask=None, labels=None):
        _, pooled = self.ernie(input_ids, token_type_ids, task_type_ids,
                               attn_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            return logits, F.cross_entropy(logits, labels)
        return logits


class ErnieForMaskedLM(nn.Layer):
    """Knowledge-masked LM head (tied decoder); the span masking itself is a
    data-pipeline concern — labels arrive with -100 on unmasked positions."""

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.ernie = ErnieModel(config)
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_norm = nn.LayerNorm(config.hidden_size,
                                           epsilon=config.layer_norm_epsilon)
        self.decoder_bias = self.create_parameter(
            shape=[config.vocab_size], is_bias=True,
            default_initializer=nn.initializer.Constant(0.0))

    def forward(self, input_ids, token_type_ids=None, task_type_ids=None,
                attn_mask=None, labels=None):
        seq_out, _ = self.ernie(input_ids, token_type_ids, task_type_ids,
                                attn_mask)
        x = self.transform_norm(F.gelu(self.transform(seq_out)))
        from .. import ops
        logits = ops.matmul(x, self.ernie.embeddings.base.word_embeddings.weight,
                            transpose_y=True) + self.decoder_bias
        if labels is not None:
            v = logits.shape[-1]
            return logits, F.cross_entropy(
                logits.reshape([-1, v]), labels.reshape([-1]),
                ignore_index=-100)
        return logits
