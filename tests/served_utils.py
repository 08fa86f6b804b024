"""A decoder that declares something else to `DecodeEngine` than its
class does (models/served.py): what the tests of the seam subclass."""
import dataclasses


def declaring(kind, **fields):
    """The subclass of the decoder `kind` whose `served()` is `kind`'s
    with `fields` replaced."""
    def served(self):
        return dataclasses.replace(kind.served(self), **fields)
    return type(kind.__name__ + 'Declaring', (kind,), {'served': served})
