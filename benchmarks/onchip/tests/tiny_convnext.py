"""The program's ConvGraph of `data/tiny_convnext.json`: ConvNeXt's
blocks at widths 8/16/16/32 and depths 1/1/2/1."""


def graph():
    from repro.models.cnn import convnext_graph

    return convnext_graph(depths=(1, 1, 2, 1), widths=(8, 16, 16, 32),
                          name="tiny_convnext")
