"""Backend compiles jax reported between the window's open and close."""


def read(obs):
    return obs["compiled_in_window"]["compiles"]
