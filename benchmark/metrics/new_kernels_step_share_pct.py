"""Share of the device's busy time in the traced slice that the kernels of
the new mechanisms take: the KDA scans (`kda_*`), flash on the latent-
attention shapes and the experts' grouped products. Says whether the new
mechanisms do most of the work of the step. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark import named
from benchmark.arch.kimi_linear import readers


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = named.kernel_seconds(trace, *readers.KDA, *readers.FLASH, *readers.GMM)
    return 100.0 * spent / trace["busy_s"] if spent else None
