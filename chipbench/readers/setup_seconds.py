"""Process start to the window's start, compilation included."""


def read(obs):
    return obs["setup_s"]
