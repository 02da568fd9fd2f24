"""Backend compiles inside the window; anything but 0 is a finding."""


def read(facts):
    return facts["compile"]["window_compiles"]
