"""Kimi-Linear (KDA + NoPE MLA + sigmoid-routed SwiGLU experts): `weights`
(leaf specs and the program's model with the seed's weights), `reference`
(float32, token-by-token) and `roofline` (required operations and bytes)."""
