"""The DeepSeek-V3 family (`model_type` `deepseek_v3`; Moonlight-16B-A3B is
the configuration the benchmark runs): latent attention with rotated key
channels in every layer, sigmoid-routed SwiGLU experts with shared experts,
an untied head. `weights` (leaf specs and the program's model with the
seed's weights), `reference` (float32), `roofline` (required operations and
bytes), `readers` (what this architecture's per-layer metrics share) and
`limits` (the control and the faults a cell's limits are set from)."""
