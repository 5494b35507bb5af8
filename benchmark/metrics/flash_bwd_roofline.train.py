"""Flash attention BACKWARD in the train step: least time for the layers'
attention backward over the steps traced (roofline.py: five products where
the forward has two) over the device time of the two kernels the program
names `flash_dq` and `flash_dkv`. Layer: kernels. Moves
train_tokens_per_s_per_chip."""
from benchmark import named, roofline


def read(run):
    cell = run["cell"]
    peak = roofline.peaks(run["device"]["kind"])
    work = roofline.flash_bwd(cell["model"], cell["mix"]["rows"], cell["mix"]["seq_len"])
    least = cell["model"]["num_hidden_layers"] / cell["chips"] * roofline.least_seconds(*work, peak)[0]
    return named.roofline_share(run, least, "flash_dq", "flash_dkv")
