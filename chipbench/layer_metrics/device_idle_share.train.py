"""Share of the traced span in which no operation ran on the device (%)."""


def read(obs):
    t = obs.get("trace")
    if not t or not t["busy_s"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
