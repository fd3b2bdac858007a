"""The benchmark's yardstick: loading cells by name, traffic, the plain
references' FAVAS loop, counting, trace reduction and the comparison that
decides ``correct``. Nothing in the program imports this package."""
