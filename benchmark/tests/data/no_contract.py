"""A reference no configuration may name: its ``Reference`` cannot say
what it supports."""


class Reference:
    def __init__(self, data, values, limits):
        pass

    def answer(self, sub):
        raise NotImplementedError
