"""One module per kind of traffic file (``"kind"``): how such a cell is
driven, summarised and checked.  Found by name."""
