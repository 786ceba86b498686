"""A value the runner took with its own clock or count, by key."""


def read(obs, key):
    return obs.get(key)
