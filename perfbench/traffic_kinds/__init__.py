"""Traffic generators, one module per ``kind`` named in a traffic file."""
