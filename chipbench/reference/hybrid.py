"""Plain float32 reference of the repository's hybrid Mamba-2 family
(``ARCHS["zamba2-1.2b"]``).  No cell runs it yet.  A hybrid cell at
published widths (Zamba2-7B-Instruct, or Zamba2-1.2B) needs the program
changed first, not only a cell: separate Mamba and attention head counts,
B/C groups, ``hybrid_layer_ids`` and adapter sizes in
``configs/base.py``; grouped B/C, ``dt_bias``, ``A`` and the ``D`` skip in
``models/mamba2.py`` and ``kernels/ssd_scan.py``; shared blocks (two,
alternating, in Zamba2-7B) fed ``concat(hidden, embedding)``, with
per-application LoRA adapters and a gated-GELU FFN, in
``models/transformer.py``; and, for Zamba2-7B, head dim 224 in the flash
path.  Its reference is then a family module of its own, whose blocks
read the embedding output (``common.layer``).

``n_layers`` pre-norm Mamba-2 blocks; after every ``shared_attn_every``-th
of them the one weight-shared attention + SwiGLU block (its weights drawn
once, from the shared-block key) is applied again; then a final RMSNorm
and an untied output head.

Where the repository's blocks, and so this reference, depart from the
published Zamba2-1.2B:

* the shared block takes the hidden state alone, where the published
  model feeds it the hidden state concatenated with the original
  embedding (so its attention is 2 x d_model wide there);
* the shared block has no per-application LoRA adapters;
* RMSNorm with unit weight everywhere, and rotary positions on the whole
  attention head;
* the Mamba-2 decay is a scalar per head with ``dt_bias`` 0 and
  ``A = -1``, B and C are shared by all heads (one group), and there is
  no skip term ``D x``;
* the Mamba-2 mixer and the shared attention take their head count
  from one ``n_heads`` (32 heads of 128 and 32 of 64 at d_model 2048),
  where the published model has 64 Mamba heads of 64 and 32 attention
  heads of 128 over the 4096-wide concatenation;
* the shared block's FFN is SwiGLU, where the published one is a gated
  GELU.
"""
from __future__ import annotations

from .common import Model, layer_keys, root_keys


def plan(m: Model, seed: int) -> list:
    _, _, k_shared, k_layers = root_keys(seed)
    keys = layer_keys(k_layers, m.n_layers)
    out = []
    for i in range(m.n_layers):
        out.append(("mamba", keys[i]))
        if m.shared_attn_every and (i + 1) % m.shared_attn_every == 0:
            out.append(("attn", k_shared))
    return out
