"""The benchmark's yardstick: manifest lookup, device and peak table, trace
reduction, work counts, seeded weights and traffic, and the comparison that
decides ``correct``. Nothing here is imported by the program under test."""
