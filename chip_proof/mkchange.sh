#!/bin/bash
# the change as git would commit it: the index, unpacked under chip_proof/change
set -e
cd /root/repo
git add -A
rm -rf chip_proof/change && mkdir -p chip_proof/change
git archive $(git write-tree) | tar -x -C chip_proof/change
for side in parent change; do ln -sfn ../.cache chip_proof/$side/.cache; done
