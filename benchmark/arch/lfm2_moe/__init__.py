"""LFM2-MoE (gated short convolutions 3:1 with QK-normed GQA, sigmoid-routed
SwiGLU experts without a shared expert, tied head): `weights` (leaf specs and
the program's model with the seed's weights), `reference` (float32),
`roofline` (required operations and bytes), `readers` (what this
architecture's per-layer metrics share) and `limits` (the control and the
faults a cell's limits are set from)."""
