"""One module per traffic kind, found by the ``kind`` of a traffic file."""
