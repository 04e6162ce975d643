"""BERT encoder LM — the to_static benchmark config (BASELINE.json config 2).

Post-LN transformer encoder per the original BERT recipe, with MLM + NSP pretraining
heads. Built on paddle_tpu.nn (reference surface: nn.TransformerEncoder,
/root/reference/python/paddle/nn/layer/transformer.py:137 — full architectures live in
PaddleNLP; here they are first-class benchmark models).
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from .. import ops


@dataclass
class BertConfig:
    vocab_size: int = 30528            # 30522 padded to a multiple of 64
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-12
    initializer_range: float = 0.02


def bert_base(**overrides) -> "BertConfig":
    cfg = dict()
    cfg.update(overrides)
    return BertConfig(**cfg)


def bert_tiny(**overrides) -> "BertConfig":
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
               intermediate_size=128, max_position_embeddings=128)
    cfg.update(overrides)
    return BertConfig(**cfg)


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.position_embeddings = nn.Embedding(config.max_position_embeddings,
                                                config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size,
                                                  config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        b, s = input_ids.shape
        pos = ops.arange(0, s, dtype="int32").unsqueeze(0)
        emb = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is not None:
            emb = emb + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(emb))


class BertLayer(nn.Layer):
    """Post-LN encoder block (attention → add&norm → FFN → add&norm)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.qkv_proj = nn.Linear(config.hidden_size, 3 * config.hidden_size)
        self.out_proj = nn.Linear(config.hidden_size, config.hidden_size)
        self.attn_norm = nn.LayerNorm(config.hidden_size,
                                      epsilon=config.layer_norm_epsilon)
        self.fc_in = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc_out = nn.Linear(config.intermediate_size, config.hidden_size)
        self.ffn_norm = nn.LayerNorm(config.hidden_size,
                                     epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self.attn_dropout_p = config.attention_dropout_prob

    def forward(self, x, attn_mask=None, seq_lens=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        if attn_mask is None and seq_lens is None:
            # packed path: attention reads the projection output in place
            # (head-pair kernels at head_dim 64 — no [B,L,H,D] relayouts)
            attn = F.flash_attention_qkv_packed(
                qkv, self.num_heads, causal=False,
                dropout=self.attn_dropout_p, training=self.training)
        else:
            qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
            q, k, v = qkv.unbind(2)
            attn = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, kv_lens=seq_lens,
                dropout_p=self.attn_dropout_p if self.training else 0.0)
            attn = attn.reshape([b, s, h])
        attn = self.out_proj(attn)
        # fused residual epilogue: LayerNorm(x + dropout(sub)) in one Pallas
        # pass on TPU (F.add_dropout_ln; unfused composition elsewhere) —
        # the reference's fused_attention/fused_feedforward epilogue analog
        x = F.add_dropout_ln(x, attn, self.attn_norm.weight,
                             self.attn_norm.bias, p=self.dropout.p,
                             epsilon=self.attn_norm._epsilon,
                             training=self.training)
        # tanh-approximate gelu: |tanh-form - erf-form| <= ~1e-3, below
        # bf16 activation rounding (~8e-3 relative) — and the erf
        # polynomial costs ~2x the VPU ops (measured 16 ms/step at
        # BERT-base B=64); reference nn.GELU(approximate=True) parity
        ffn = self.fc_out(F.gelu(self.fc_in(x), approximate=True))
        return F.add_dropout_ln(x, ffn, self.ffn_norm.weight,
                                self.ffn_norm.bias, p=self.dropout.p,
                                epsilon=self.ffn_norm._epsilon,
                                training=self.training)


class BertModel(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        self.encoder = nn.LayerList([BertLayer(config)
                                     for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)
        self._init_weights(config)

    def _init_weights(self, config):
        normal = nn.initializer.Normal(mean=0.0, std=config.initializer_range)
        for _, p in self.named_parameters():
            if p.ndim >= 2:
                p.set_value(normal(tuple(p.shape), p.dtype))

    def forward(self, input_ids, token_type_ids=None, attn_mask=None,
                seq_lens=None):
        # seq_lens ([B] int): per-sequence valid-token counts — the structured
        # (Pallas-flash) form of the usual [B,1,1,L] padding attn_mask
        x = self.embeddings(input_ids, token_type_ids)
        for layer in self.encoder:
            x = layer(x, attn_mask, seq_lens)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPreTraining(nn.Layer):
    """MLM (tied decoder) + NSP heads; forward returns (mlm_logits, nsp_logits) or the
    summed pretraining loss when labels are given."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.bert = BertModel(config)
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_norm = nn.LayerNorm(config.hidden_size,
                                           epsilon=config.layer_norm_epsilon)
        self.decoder_bias = self.create_parameter(
            shape=[config.vocab_size], is_bias=True,
            default_initializer=nn.initializer.Constant(0.0))
        self.nsp_head = nn.Linear(config.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None,
                masked_lm_labels=None, next_sentence_labels=None,
                seq_lens=None):
        seq_out, pooled = self.bert(input_ids, token_type_ids, attn_mask,
                                    seq_lens)
        x = self.transform_norm(F.gelu(self.transform(seq_out),
                                       approximate=True))
        nsp_logits = self.nsp_head(pooled)
        if masked_lm_labels is None:
            mlm_logits = ops.matmul(
                x, self.bert.embeddings.word_embeddings.weight,
                transpose_y=True) + self.decoder_bias
            return mlm_logits, nsp_logits
        # fused head+CE (gpt.py lm_head_ce): the [B,S,V] fp32 logits never
        # materialize on the loss path — at BERT-base that's a 2GB tensor
        from ..ops._helpers import _op
        mlm_loss = _op("lm_head_ce", x, self.bert.embeddings.word_embeddings
                       .weight, masked_lm_labels, self.decoder_bias,
                       transpose_w=True, has_bias=True)
        loss = mlm_loss
        if next_sentence_labels is not None:
            loss = loss + F.cross_entropy(nsp_logits,
                                          next_sentence_labels.reshape([-1]))
        return loss
