"""Wall of generating the data, ``Dataset(...)`` and ``bind(...)``."""


def read(facts):
    return facts["data_prep_s"]
