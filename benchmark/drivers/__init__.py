"""One module per driver kind (`fit`, `generate`), found by the cell's `driver`."""
