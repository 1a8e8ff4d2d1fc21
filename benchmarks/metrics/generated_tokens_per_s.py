"""Output tokens GENERATED inside the window / its seconds: a request's first
token at due + ttft, the rest spread evenly up to the moment ``step()`` handed
its record back. Below the knee it follows the offered load and can only show
a server that fell behind; which long answers straddle the window's edges
moves it by 4-6 % between seeds, so it is recorded, not bounded. Moves
tpot_p50_s (a server that falls behind fills its slots)."""


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve":
        return None
    return a["output_tokens_in_window"] / a["window_s"]
