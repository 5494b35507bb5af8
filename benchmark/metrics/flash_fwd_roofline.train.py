"""Flash attention FORWARD in the train step: the least time the chip could
take for the layers' causal attention over the steps traced (roofline.py;
compute-bound at 4096) over the device time of the kernel the program names
`flash_fwd`. A forward recomputed in the backward is time spent and not work
required, so it lowers the share. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark import named, roofline


def read(run):
    cell = run["cell"]
    peak = roofline.peaks(run["device"]["kind"])
    work = roofline.flash_fwd(cell["model"], cell["mix"]["rows"], cell["mix"]["seq_len"])
    # the whole batch's attention, shared evenly by the chips
    least = cell["model"]["num_hidden_layers"] / cell["chips"] * roofline.least_seconds(*work, peak)[0]
    return named.roofline_share(run, least, "flash_fwd")
