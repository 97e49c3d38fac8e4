"""Device time of one ``_gram_operator`` program (the d×d ``eigh``, the
Rayleigh-Ritz step and the projection to the rank-r operator), in ms: its
``XLA Modules`` events in the traced window over their count."""


def read(run):
    prog = (run.trace or {}).get("programs", {}).get("_gram_operator")
    if not prog or not prog["calls"]:
        return None
    return 1e3 * prog["seconds"] / prog["calls"]
