"""Share of the recurrent-state pool's rows in use (%): the mean of the
engine's ``state_rows_in_use`` gauge, polled once a second over the
window, over ``max_slots``.  Every row's state is resident whether used
or not; this is how much of that memory (and of the decode program's
state traffic) serves a live session."""


def read(obs):
    if obs.get("state_rows_mean") is None or not obs.get("max_slots"):
        return None
    return 100.0 * obs["state_rows_mean"] / obs["max_slots"]
