"""Share of the training window the host spent fetching, collating and
placing the next batch (the harness's ``input`` span around next(batches) +
place_batch), while the device had nothing queued. Moves
train_tokens_per_s_per_chip."""


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "train":
        return None
    t0, t1 = run["window"]
    return 100.0 * run["spans"].total("input", t0, t1) / (t1 - t0)
