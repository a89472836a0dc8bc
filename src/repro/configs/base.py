"""ModelConfig — one dataclass covering all assigned architecture
families."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.layers import MacConfig


def _pad_to(x: int, m: int) -> int:
    return x if m <= 1 else ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch: str = "tiny"
    family: str = "dense"        # dense|moe|xlstm|hybrid|encdec|vlm
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: Optional[int] = None
    d_ff: int = 512
    vocab_size: int = 1024
    act: str = "silu"
    gated_mlp: bool = True
    norm: str = "rms"
    norm_eps: float = 1e-6
    qkv_bias: bool = False
    attn_out_bias: bool = False
    mlp_bias: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    # yarn-scaled rotary positions on the full-attention layers, as
    # transformers' ``_compute_yarn_parameters`` defines them; layers with
    # a sliding window keep plain ``rope_theta``.  0 = no scaling
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_attention_factor: Optional[float] = None  # None: 0.1·ln f + 1
    tie_embeddings: bool = False
    embed_scale: bool = False
    post_norm: bool = False           # gemma2 sandwich norms
    qk_norm: bool = False             # qwen3
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    attn_scale: Optional[float] = None
    sliding_window: Optional[int] = None
    local_global_period: int = 0      # gemma2: 2 → every 2nd layer local
    global_layers: Tuple[int, ...] = ()  # hymba: indices with global attn
    # moe
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    first_k_dense: int = 0
    router_type: str = "softmax"
    norm_topk: bool = True
    aux_loss_weight: float = 0.01
    # mla
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = False
    mtp: bool = False
    mtp_weight: float = 0.3
    # ssm / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    meta_tokens: int = 0
    # xlstm
    slstm_every: int = 0              # 1 sLSTM per N blocks (0 → none)
    mlstm_proj_factor: float = 2.0
    chunk_size: int = 256
    # encdec (whisper)
    enc_layers: int = 0
    dec_layers: int = 0
    enc_len_ratio: int = 4
    max_pos_embed: int = 32768
    # vlm
    n_patches: int = 0
    # execution
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    attn_chunk: int = 1024
    # Pallas flash kernel for full-sequence attention: '' = off; 'pallas'
    # = Mosaic (TPU only); 'pallas_interpret' = interpret mode (tests)
    flash_attention: str = ""
    # paged decode attention (DESIGN.md §8): 'xla' = gathered-view
    # reference; 'pallas' = fused flash-decoding kernel over the page
    # table, compiled by Mosaic (TPU only — raises elsewhere);
    # 'pallas_interpret' = that kernel in interpret mode (tests);
    # 'blocked' = its XLA lowering; 'auto' = pallas on TPU, else blocked
    attention_backend: str = "xla"
    # max query tokens per slot routed through the fused paged kernel:
    # 1 = decode only (default); the speculative-decoding verify step
    # (DESIGN.md §10) raises it to k+1 so batched k-token scoring stays
    # on the fused path (longer chunks still use the gather path)
    paged_fused_max_sq: int = 1
    # paged KV-cache storage (DESIGN.md §11): 'bf16' = dense pages in
    # compute_dtype (the historical layout); 'int8'/'int4' store pages
    # quantized with per-token per-kv-head f32 scale rows in side pools,
    # dequantized inside the paged-attention page loop (2–4x fewer pool
    # bytes per token → more slots / longer contexts at equal HBM)
    kv_cache_dtype: str = "bf16"
    remat: bool = True
    pad_heads_to: int = 1
    vocab_pad_to: int = 1
    scan_layers: bool = True
    unroll_scans: bool = False   # cost probes: python-loop inner scans
    microbatch: int = 0          # global microbatch for grad accumulation
    mac: MacConfig = dataclasses.field(default_factory=MacConfig)
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    fsdp: bool = False

    def __post_init__(self):
        # a configuration read from JSON gives lists; tuples keep the
        # frozen config hashable, so jitted steps memoize on it
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, list):
                object.__setattr__(self, f.name, tuple(v))

    # ---- derived ----
    @property
    def head_dim_r(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_heads_p(self) -> int:
        return _pad_to(self.n_heads, self.pad_heads_to)

    @property
    def n_kv_p(self) -> int:
        return _pad_to(self.n_kv_heads, self.pad_heads_to)

    @property
    def vocab_p(self) -> int:
        return _pad_to(self.vocab_size, self.vocab_pad_to)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def layer_windows(self):
        """Per-layer sliding windows (None entries = global)."""
        n = self.dec_layers or self.n_layers
        out = []
        for i in range(n):
            if self.local_global_period:
                out.append(self.sliding_window
                           if i % self.local_global_period == 0 else None)
            elif self.global_layers:
                out.append(None if i in self.global_layers
                           else self.sliding_window)
            else:
                out.append(self.sliding_window)
        return out

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            n_layers=min(self.n_layers, 2),
            enc_layers=min(self.enc_layers, 2),
            dec_layers=min(self.dec_layers, 2),
            d_model=128,
            n_heads=max(2, min(self.n_heads, 4)),
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            head_dim=32,
            d_ff=256, d_ff_expert=64 if self.d_ff_expert else 0,
            vocab_size=512,
            n_experts=min(self.n_experts, 8),
            top_k=min(self.top_k, 2),
            first_k_dense=min(self.first_k_dense, 1),
            q_lora_rank=48 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_dim=32 if self.qk_nope_dim else 0,
            qk_rope_dim=16 if self.qk_rope_dim else 0,
            v_head_dim=32 if self.v_head_dim else 0,
            sliding_window=min(self.sliding_window, 64)
            if self.sliding_window else None,
            meta_tokens=min(self.meta_tokens, 8),
            n_patches=min(self.n_patches, 16),
            chunk_size=32, attn_chunk=64, max_pos_embed=2048,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            global_layers=tuple(g for g in self.global_layers if g < 2),
            param_dtype="float32", compute_dtype="float32",
            pad_heads_to=1, vocab_pad_to=1, fsdp=False)
