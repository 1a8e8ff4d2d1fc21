"""Busy slots over slots, sampled by the harness after every engine.step()
of the window, averaged. Moves tpot_p50_s."""


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "serve" or not a["steps"]:
        return None
    return 100.0 * sum(s[2] for s in a["steps"]) / len(a["steps"]) / a["slots"]
